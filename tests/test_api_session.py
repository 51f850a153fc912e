"""PageRankSession lifecycle, EngineConfig/registry validation, deprecation
shims (warning + bit-for-bit routing parity), fork semantics, and the
multi-session service."""
import dataclasses
import gc
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.api import (EngineConfig, PageRankService, PageRankSession,
                       ServingConfig, registry)
from repro.core import pagerank as pr
from repro.core.delta import random_batch
from repro.core.frontier import batch_to_device
from repro.graphs.generators import grid_road, rmat


@pytest.fixture(scope="module")
def dyn():
    hg0 = rmat(9, avg_degree=6, seed=5)
    g0 = hg0.snapshot(block_size=64)
    r_prev = jnp.asarray(pr.numpy_reference(g0, iterations=300))
    dels, ins = random_batch(hg0, 5e-3, seed=21)
    hg1 = hg0.apply_batch(dels, ins)
    g1 = hg1.snapshot(block_size=64)
    batch = batch_to_device(g1, dels, ins)
    return hg0, g0, hg1, g1, batch, r_prev, dels, ins


# ---------------------------------------------------------------------------
# EngineConfig validation
# ---------------------------------------------------------------------------

class TestEngineConfig:
    def test_defaults_valid(self):
        cfg = EngineConfig()
        assert cfg.resolved_engine in registry.names()
        assert cfg.resolved_backend in ("pallas", "xla")

    @pytest.mark.parametrize("kw", [
        dict(mode="nope"), dict(active_policy="nope"), dict(alpha=0.0),
        dict(alpha=1.5), dict(tau=-1e-9), dict(tau_f=0.0), dict(tile=0),
        dict(block_size=-64), dict(max_iterations=0),
        dict(engine="not-an-engine"), dict(backend="not-a-backend"),
        dict(faults=object()),
    ])
    def test_bad_values_rejected_at_construction(self, kw):
        with pytest.raises(ValueError):
            EngineConfig(**kw)

    def test_unknown_keys_rejected_with_valid_list(self):
        with pytest.raises(TypeError, match="taau.*valid keys"):
            EngineConfig.from_kwargs(taau=1e-9)
        with pytest.raises(TypeError, match="valid keys"):
            EngineConfig().replace(engin="blocked")

    def test_replace_builds_validated_variant(self):
        cfg = EngineConfig(tau=1e-8)
        cfg2 = cfg.replace(alpha=0.9)
        assert cfg2.alpha == 0.9 and cfg2.tau == 1e-8
        with pytest.raises(ValueError):
            cfg.replace(mode="nope")

    def test_tau_f_resolution(self):
        cfg = EngineConfig(tau=1e-6)
        assert cfg.resolved_tau_f(expand=True) == pytest.approx(1e-9)
        assert cfg.resolved_tau_f(expand=False) == float("inf")
        assert EngineConfig(tau_f=1e-4).resolved_tau_f(expand=True) == 1e-4

    def test_env_overrides_validated_eagerly(self, monkeypatch):
        monkeypatch.setenv("REPRO_ENGINE", "bogus")
        with pytest.raises(ValueError, match="REPRO_ENGINE.*registered"):
            EngineConfig()
        monkeypatch.delenv("REPRO_ENGINE")
        monkeypatch.setenv("REPRO_TILE_BACKEND", "bogus")
        with pytest.raises(ValueError, match="REPRO_TILE_BACKEND"):
            EngineConfig()
        monkeypatch.setenv("REPRO_TILE_BACKEND", "xla")
        assert EngineConfig().resolved_backend == "xla"

    def test_env_override_accepts_registered_engine(self, monkeypatch):
        monkeypatch.setenv("REPRO_ENGINE", "dense")
        assert EngineConfig().resolved_engine == "dense"
        assert pr.default_engine() == "dense"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_unknown_engine_error_lists_registered(self):
        with pytest.raises(ValueError, match="blocked.*dense.*pallas"):
            registry.resolve("not-an-engine")

    def test_custom_engine_registers_and_resolves(self):
        class EchoEngine:
            name = "echo-test"

            def run(self, g, R0, affected0, **kw):
                from repro.core.blocked import SweepStats
                return R0, SweepStats(converged=True)

        registry.register(EchoEngine())
        try:
            assert "echo-test" in registry.names()
            assert registry.resolve("echo-test").name == "echo-test"
            with pytest.raises(ValueError, match="already registered"):
                registry.register(EchoEngine())
        finally:
            registry._REGISTRY.pop("echo-test", None)

    def test_invalid_adapters_rejected(self):
        class NoName:
            def run(self):
                pass

        with pytest.raises(ValueError, match="name"):
            registry.register(NoName())

    def test_non_pallas_engines_reject_tile_operands(self, dyn):
        _, g0, _, _, _, r_prev, _, _ = dyn
        with pytest.raises(ValueError, match="only consumed by "
                                             "engine='pallas'"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                pr.nd_pagerank(g0, r_prev, engine="blocked",
                               pallas_backend="xla")


# ---------------------------------------------------------------------------
# deprecation shims: warning + bit-for-bit session parity
# ---------------------------------------------------------------------------

class TestDeprecationShims:
    """Each legacy variant function must emit DeprecationWarning, route
    through PageRankSession, and match the session call bit-for-bit."""

    ENGINE = "blocked"      # deterministic + fast on CPU containers

    def _cfg(self, mode):
        return EngineConfig(mode=mode, engine=self.ENGINE)

    def test_static(self, dyn):
        _, g0, _, _, _, _, _, _ = dyn
        with pytest.warns(DeprecationWarning, match="static_pagerank"):
            res = pr.static_pagerank(g0, mode="bb", engine=self.ENGINE)
        sess = PageRankSession.from_snapshot(g0, config=self._cfg("bb"))
        out = sess.recompute("static")
        assert np.array_equal(np.asarray(res.ranks), np.asarray(out.ranks))
        assert res.stats.sweeps == out.stats.sweeps

    def test_nd(self, dyn):
        _, g0, _, _, _, r_prev, _, _ = dyn
        with pytest.warns(DeprecationWarning, match="nd_pagerank"):
            res = pr.nd_pagerank(g0, r_prev, mode="lf", engine=self.ENGINE)
        sess = PageRankSession.from_snapshot(g0, config=self._cfg("lf"),
                                             r0=r_prev)
        out = sess.recompute("nd")
        assert np.array_equal(np.asarray(res.ranks), np.asarray(out.ranks))
        assert res.stats.sweeps == out.stats.sweeps

    def test_dt(self, dyn):
        hg0, g0, _, g1, batch, r_prev, dels, ins = dyn
        with pytest.warns(DeprecationWarning, match="dt_pagerank"):
            res = pr.dt_pagerank(g0, g1, batch, r_prev, mode="lf",
                                 engine=self.ENGINE)
        sess = PageRankSession.from_graph(hg0, config=self._cfg("lf"),
                                          r0=r_prev)
        out = sess.update(dels, ins, variant="dt")
        assert np.array_equal(np.asarray(res.ranks), np.asarray(out.ranks))
        assert res.stats.sweeps == out.stats.sweeps

    def test_df(self, dyn):
        hg0, g0, _, g1, batch, r_prev, dels, ins = dyn
        with pytest.warns(DeprecationWarning, match="df_pagerank"):
            res = pr.df_pagerank(g0, g1, batch, r_prev, mode="lf",
                                 engine=self.ENGINE)
        sess = PageRankSession.from_graph(hg0, config=self._cfg("lf"),
                                          r0=r_prev)
        out = sess.update(dels, ins, variant="df")
        assert np.array_equal(np.asarray(res.ranks), np.asarray(out.ranks))
        assert res.stats.sweeps == out.stats.sweeps

    def test_df_recompute_replays_last_batch(self, dyn):
        """recompute('df') after update == the update itself (same marking,
        same pre-batch ranks)."""
        hg0, _, _, _, _, r_prev, dels, ins = dyn
        sess = PageRankSession.from_graph(hg0, config=self._cfg("lf"),
                                          r0=r_prev)
        out = sess.update(dels, ins, variant="df")
        replay = sess.recompute("df")
        assert np.array_equal(np.asarray(out.ranks),
                              np.asarray(replay.ranks))

    def test_recompute_dt_df_require_a_batch(self, dyn):
        hg0, _, _, _, _, r_prev, _, _ = dyn
        sess = PageRankSession.from_graph(hg0, config=self._cfg("lf"),
                                          r0=r_prev)
        with pytest.raises(ValueError, match="no batch"):
            sess.recompute("df")
        # warmup's internal empty batch must not count as "the last update"
        stream = PageRankSession.from_graph(
            hg0, config=EngineConfig(engine="pallas", block_size=64),
            r0=r_prev)
        stream.warmup()
        with pytest.raises(ValueError, match="no batch"):
            stream.recompute("dt")


# ---------------------------------------------------------------------------
# session lifecycle
# ---------------------------------------------------------------------------

class TestSessionLifecycle:
    def test_from_graph_initial_solve_matches_reference(self, dyn):
        hg0, g0, _, _, _, _, _, _ = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(engine="pallas", block_size=64))
        ref = pr.numpy_reference(g0, iterations=300)
        assert pr.linf(sess.R[:g0.n], jnp.asarray(ref[:g0.n])) < 1e-8

    def test_compiled_pallas_kernels_reject_f64_at_construction(self, dyn):
        """The compiled Pallas kernels (a TPU's, interpret=False) have no
        float64: the session refuses at construction, naming the fix,
        instead of failing inside the first compile or casting down."""
        hg0 = dyn[0]
        cfg = EngineConfig(engine="pallas", backend="pallas", block_size=64)
        assert cfg.resolved_dtype() == jnp.float64      # the suite's x64
        with pytest.raises(ValueError, match="float64"):
            PageRankSession.from_graph(hg0, config=cfg, interpret=False)
        with pytest.raises(ValueError, match="float64"):
            PageRankSession.from_snapshot(hg0.snapshot(block_size=64),
                                          config=cfg, interpret=False)

    def test_bare_snapshot_session_cannot_update(self, dyn):
        _, g0, _, _, _, r_prev, dels, ins = dyn
        sess = PageRankSession.from_snapshot(
            g0, config=EngineConfig(engine="blocked"), r0=r_prev)
        with pytest.raises(ValueError, match="from_graph"):
            sess.update(dels, ins)

    def test_bad_variant_rejected(self, dyn):
        hg0, _, _, _, _, r_prev, dels, ins = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(engine="blocked"), r0=r_prev)
        with pytest.raises(ValueError, match="variant"):
            sess.update(dels, ins, variant="nope")
        with pytest.raises(ValueError, match="variant"):
            sess.recompute("nope")

    def test_config_type_checked(self, dyn):
        hg0 = dyn[0]
        with pytest.raises(TypeError, match="EngineConfig"):
            PageRankSession.from_graph(hg0, config={"alpha": 0.9})

    def test_stream_variants_match_snapshot_oracles(self, dyn):
        """nd/static variants through the stream-mode hot path agree with
        the legacy snapshot-based route."""
        hg0, g0, hg1, g1, batch, r_prev, dels, ins = dyn
        for variant, oracle in (
                ("nd", lambda: pr.nd_pagerank(g1, r_prev, mode="lf",
                                              engine="pallas")),
                ("static", lambda: pr.static_pagerank(g1, mode="lf",
                                                      engine="pallas"))):
            sess = PageRankSession.from_graph(
                hg0, config=EngineConfig(engine="pallas", block_size=64),
                r0=r_prev)
            res = sess.update(dels, ins, variant=variant)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                ref = oracle()
            assert res.stats.converged
            assert pr.linf(res.ranks, ref.ranks) < 1e-12, variant

    def test_concurrent_bucket_compile_not_charged_as_retrace(
            self, dyn, monkeypatch):
        """The fused driver's jit cache is process-wide: a first-visit
        bucket compile by a CONCURRENT session can land inside this
        session's cache-delta window (service dispatch overlaps drives).
        Growth explained by an overlapping first-visit drive must be
        classified as bucket-ladder growth, not an unexpected retrace —
        and growth with no overlapping drive must still be charged."""
        from repro.api import session as sess_mod
        hg0, _, _, _, _, r_prev, dels, ins = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(engine="pallas", block_size=64),
            r0=r_prev)
        sess.update(dels, ins)          # warm: own ladder bucket visited
        real = sess_mod._driver_cache_size
        calls = {"n": 0}

        def growing():                  # every cache1 read sees one entry
            calls["n"] += 1             # more than its cache0 — a compile
            return real() + (1 if calls["n"] % 2 == 0 else 0)

        monkeypatch.setattr(sess_mod, "_driver_cache_size", growing)
        d2, i2 = random_batch(sess.hg, 5e-3, seed=91)
        res = sess.update(d2, i2)       # no overlapping first-visit drive
        assert res.driver_retraces == 1  # → charged as a real retrace
        assert res.bucket_retraces == 0
        monkeypatch.setattr(sess_mod, "_NEW_BUCKET_ACTIVE", 1)
        d3, i3 = random_batch(sess.hg, 5e-3, seed=92)
        res = sess.update(d3, i3)       # concurrent first-visit drive
        assert res.driver_retraces == 0  # explains the growth
        assert res.bucket_retraces == 1

    def test_history_holds_no_device_array_per_update(self, dyn):
        """The session keeps each update's stats, not its ranks: the device
        arrays alive stay as many over a long stream."""
        hg = dyn[0]
        sess = PageRankSession.from_graph(
            hg, config=EngineConfig(engine="pallas", block_size=64))
        sess.warmup()
        batches = []
        for i in range(22):
            dels, ins = random_batch(hg, 2e-3, seed=100 + i)
            batches.append((dels, ins))
            hg = hg.apply_batch(dels, ins)
        for dels, ins in batches[:2]:
            sess.update(dels, ins)
        gc.collect()
        alive = len(jax.live_arrays())
        for dels, ins in batches[2:]:
            assert sess.update(dels, ins).ranks is not None
        gc.collect()
        assert len(jax.live_arrays()) <= alive
        rep = sess.report()
        assert rep.n_updates == 22 and len(rep.sweeps_history) == 22
        assert sess._history[-1].ranks is None
        sess.close()

    def test_fork_branches_are_independent(self, dyn):
        hg0, _, _, _, _, r_prev, dels, ins = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(engine="pallas", block_size=64),
            r0=r_prev)
        base_m = sess.hg.m
        base_R = np.asarray(sess.R).copy()
        twin = sess.fork()
        assert twin.inc.mat.tiles is sess.inc.mat.tiles  # shared tile pool
        twin.update(dels, ins)
        # parent untouched by the fork's update
        assert sess.hg.m == base_m
        np.testing.assert_array_equal(np.asarray(sess.R), base_R)
        np.testing.assert_array_equal(np.asarray(sess._out_deg),
                                      np.asarray(
                                          sess.hg.snapshot(
                                              block_size=64).out_deg))
        # both branches keep converging independently
        d2, i2 = random_batch(sess.hg, 5e-3, seed=77)
        assert sess.update(d2, i2).stats.converged
        assert twin.report().n_updates == 1
        assert sess.report().n_updates == 1


    def test_fork_bmat_twins_are_independent(self):
        """The host presence map is patched in place per batch, so each
        branch of a fork owns its copy: a batch on one leaves the other's
        map as it was, in both directions."""
        sess = PageRankSession.from_graph(
            grid_road(32, seed=0),
            config=EngineConfig(engine="pallas", block_size=64))
        twin = sess.fork()
        base = sess.inc.aux.bmat.copy()
        none = np.zeros((0, 2), np.int64)
        # one edge (src, dst) into each of two cells (dst-block, src-block)
        # that are still off
        (r0, c0), (r1, c1) = np.argwhere(~base)[[0, -1]]
        sess.update(none, np.array([[c0 * 64 + 5, r0 * 64 + 3]]))
        assert sess.inc.aux.bmat[r0, c0]
        np.testing.assert_array_equal(twin.inc.aux.bmat, base)
        after = sess.inc.aux.bmat.copy()
        twin.update(none, np.array([[c1 * 64 + 5, r1 * 64 + 3]]))
        assert twin.inc.aux.bmat[r1, c1] and not twin.inc.aux.bmat[r0, c0]
        np.testing.assert_array_equal(sess.inc.aux.bmat, after)


# ---------------------------------------------------------------------------
# service: N sessions, one queue
# ---------------------------------------------------------------------------

class TestService:
    def test_drains_and_reports_per_session(self):
        graphs = [rmat(8, avg_degree=4, seed=s) for s in (0, 1)]
        svc = PageRankService(
            graphs, config=EngineConfig(engine="pallas", block_size=64),
            serving=ServingConfig(coalesce=False))
        cur = list(graphs)
        for j in range(2):
            for i in range(len(cur)):
                dels, ins = random_batch(cur[i], 1e-2, seed=50 + 10 * i + j)
                svc.submit(i, dels, ins)
                cur[i] = cur[i].apply_batch(dels, ins)
        done = svc.run_until_drained()
        assert len(done) == 4
        assert all(r.done and r.result.stats.converged for r in done)
        assert all(r.latency_s >= r.wait_s >= 0 for r in done)
        rep = svc.report()
        assert rep["requests_done"] == 4 and rep["requests_queued"] == 0
        for row in rep["sessions"]:
            assert row["n_updates"] == 2
            # sessions share the jit caches → no session retraces after
            # the service-level warmup
            assert row["retraces_post_warmup"] == 0
        # session ranks match an independent oracle on the final graphs
        for i, hg in enumerate(cur):
            ref = pr.numpy_reference(hg.snapshot(block_size=64),
                                     iterations=300)
            n = svc.sessions[i].n
            assert pr.linf(svc.sessions[i].R[:n],
                           jnp.asarray(ref[:n])) < 1e-8

    def test_finished_requests_hold_no_rank_vector(self):
        """A finished request keeps its update's record without the [n_pad]
        ranks: the device arrays alive stay as many however many
        dispatches the service has finished."""
        hg = rmat(8, avg_degree=4, seed=3)
        svc = PageRankService(
            [hg], config=EngineConfig(engine="pallas", block_size=64),
            serving=ServingConfig(coalesce=False))
        batches = []
        for i in range(12):
            dels, ins = random_batch(hg, 1e-2, seed=300 + i)
            batches.append((dels, ins))
            hg = hg.apply_batch(dels, ins)
        for dels, ins in batches[:2]:
            svc.submit(0, dels, ins)
        svc.run_until_drained()
        gc.collect()
        alive = len(jax.live_arrays())
        for dels, ins in batches[2:]:
            svc.submit(0, dels, ins)
        done = svc.run_until_drained()
        gc.collect()
        assert len(jax.live_arrays()) <= alive
        assert len(done) == 12
        assert all(r.result.ranks is None and r.result.stats.converged
                   for r in done)
        assert svc.sessions[0].report().n_updates == 12

    def test_step_coalesces_queue_into_one_update(self):
        hg = rmat(8, avg_degree=4, seed=2)
        svc = PageRankService(
            [hg], config=EngineConfig(engine="pallas", block_size=64))
        cur = hg
        for j in range(3):
            dels, ins = random_batch(cur, 1e-2, seed=90 + j)
            svc.submit(0, dels, ins)
            cur = cur.apply_batch(dels, ins)
        assert svc.step() == 3      # whole run retires in ONE dispatch
        assert svc.queue == []
        assert [r.uid for r in svc.finished] == [1, 2, 3]
        assert svc.sessions[0].report().n_updates == 1  # one scatter
        # last-write-wins fold equals the sequential end state
        ref = pr.numpy_reference(cur.snapshot(block_size=64),
                                 iterations=300)
        assert pr.linf(svc.sessions[0].R[:cur.n],
                       jnp.asarray(ref[:cur.n])) < 1e-8

    def test_fifo_per_stream_without_coalescing(self):
        hg = rmat(8, avg_degree=4, seed=2)
        svc = PageRankService(
            [hg], config=EngineConfig(engine="pallas", block_size=64),
            serving=ServingConfig(coalesce=False))
        cur = hg
        for j in range(3):
            dels, ins = random_batch(cur, 1e-2, seed=90 + j)
            svc.submit(0, dels, ins)
            cur = cur.apply_batch(dels, ins)
        assert svc.step() == 1          # one batch per slot per pass
        assert len(svc.queue) == 2
        assert [r.uid for r in svc.finished] == [1]
        svc.run_until_drained()
        assert [r.uid for r in svc.finished] == [1, 2, 3]

    def test_submit_bad_stream_rejected(self):
        svc = PageRankService(
            [rmat(7, avg_degree=4, seed=0)],
            config=EngineConfig(engine="pallas", block_size=64),
            warmup=False)
        with pytest.raises(ValueError, match="out of range"):
            svc.submit(3, np.zeros((0, 2)), np.zeros((0, 2)))


# ---------------------------------------------------------------------------
# topology axis: config validation + in-process sharded parity (1-shard mesh)
# ---------------------------------------------------------------------------

class TestTopologyConfig:
    @pytest.mark.parametrize("kw", [
        dict(topology="nope"),
        dict(topology="sharded", n_shards=0),
        dict(topology="sharded", n_shards=-2),
        dict(partitioner="metis"),
        dict(exchange="ring"),          # rebuild-only, not a session axis
        dict(exchange="nope"),
        dict(n_shards=4),               # needs topology="sharded"
        dict(engine="distributed"),     # topology selects the engine
        dict(topology="sharded", engine="pallas"),
    ])
    def test_bad_topology_combos_rejected(self, kw):
        with pytest.raises(ValueError):
            EngineConfig(**kw)

    def test_oversubscribed_mesh_rejected(self):
        import jax
        too_many = len(jax.devices()) + 1
        with pytest.raises(ValueError, match="exceeds"):
            EngineConfig(topology="sharded", n_shards=too_many)

    def test_sharded_rejects_fault_plans(self):
        # the sharded sweep has no crash tables (stragglers are the
        # model) — rejected at construction, not silently ignored
        from repro.core import faults as flt
        with pytest.raises(ValueError, match="fault simulation"):
            EngineConfig(topology="sharded", n_shards=1,
                         faults=flt.NO_FAULTS)

    def test_sharded_resolves_distributed_engine(self):
        cfg = EngineConfig(topology="sharded", n_shards=1)
        assert cfg.resolved_engine == "distributed"
        assert cfg.resolved_n_shards == 1
        assert EngineConfig().resolved_n_shards is None
        assert "distributed" in registry.names()

    def test_non_distributed_engines_reject_shard_spec(self, dyn):
        from repro.core.distributed import ShardSpec
        _, g0, _, _, _, r_prev, _, _ = dyn
        eng = registry.resolve("blocked")
        with pytest.raises(ValueError, match="only consumed by "
                                             "engine='distributed'"):
            eng.run(g0, r_prev, g0.vertex_valid, mode="lf", expand=False,
                    alpha=0.85, tau=1e-10, tau_f=None, max_iterations=5,
                    faults=None, tile=512, active_policy="affected",
                    shards=ShardSpec(n_shards=1))


class TestShardedSession:
    """Topology-transparent session over a 1-shard mesh (the in-process
    coverage; the 8-device parity suite lives in
    tests/test_sharded_session.py behind the `multidevice` marker)."""

    CFG = dict(topology="sharded", n_shards=1)

    def test_static_solve_matches_reference(self, dyn):
        hg0, g0, _, _, _, _, _, _ = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(partitioner="bfs_blocks", **self.CFG))
        ref = pr.numpy_reference(g0, iterations=300)
        assert pr.linf(jnp.asarray(sess.ranks[:g0.n]),
                       jnp.asarray(ref[:g0.n])) < 1e-8
        rep = sess.report()
        assert rep.topology == "sharded" and rep.n_shards == 1
        assert rep.partitioner == "bfs_blocks"
        assert 0.0 <= rep.edge_cut <= 1.0

    def test_df_stream_matches_blocked_oracle(self, dyn):
        hg0, g0, _, _, _, r_prev, _, _ = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(**self.CFG), r0=r_prev)
        oracle = PageRankSession.from_graph(
            hg0, config=EngineConfig(engine="blocked"), r0=r_prev)
        sess.warmup()
        cur = hg0
        for i in range(3):
            dels, ins = random_batch(cur, 5e-3, seed=400 + i)
            cur = cur.apply_batch(dels, ins)
            res = sess.update(dels, ins)
            ores = oracle.update(dels, ins)
            assert res.stats.converged and ores.stats.converged
            assert np.max(np.abs(sess.ranks[:cur.n]
                                 - oracle.ranks[:cur.n])) < 1e-9, i
        assert sess.report().retraces_post_warmup == 0
        assert sess.report().collective_bytes_per_sweep is not None

    def test_query_topk_translate_through_relabeling(self, dyn):
        hg0, _, _, _, _, r_prev, dels, ins = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(partitioner="hash", **self.CFG),
            r0=r_prev)
        sess.update(dels, ins)
        full = sess.ranks
        ids = [0, 3, sess.n - 1]
        np.testing.assert_allclose(sess.query(ids), full[ids])
        vals, idx = sess.top_k(4)
        np.testing.assert_allclose(vals, full[idx])
        order = np.argsort(full[:sess.n])[::-1][:4]
        np.testing.assert_allclose(vals, full[order])

    def test_recompute_variants_and_fork(self, dyn):
        hg0, _, _, _, _, r_prev, dels, ins = dyn
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(**self.CFG), r0=r_prev)
        with pytest.raises(ValueError, match="no batch"):
            sess.recompute("df")
        out = sess.update(dels, ins)
        replay = sess.recompute("df")
        np.testing.assert_array_equal(np.asarray(out.ranks),
                                      np.asarray(replay.ranks))
        static = sess.recompute("static")
        assert static.stats.converged
        twin = sess.fork()
        d2, i2 = random_batch(sess.hg, 5e-3, seed=88)
        twin.update(d2, i2)
        assert sess.report().n_updates == 1     # parent untouched
        assert twin.report().n_updates == 1
        assert sess.hg.m != twin.hg.m or not np.array_equal(
            np.asarray(sess.R), np.asarray(twin.R))


# ---------------------------------------------------------------------------
# query/top_k ergonomics + session close / context manager
# ---------------------------------------------------------------------------

class TestServingErgonomics:
    def _sess(self, dyn):
        hg0, _, _, _, _, r_prev, _, _ = dyn
        return PageRankSession.from_graph(
            hg0, config=EngineConfig(engine="blocked"), r0=r_prev)

    def test_query_accepts_python_int_and_list(self, dyn):
        sess = self._sess(dyn)
        one = sess.query(3)
        assert one.shape == (1,)
        np.testing.assert_allclose(sess.query([3, 5]),
                                   np.asarray(sess.R)[[3, 5]])
        assert sess.query([]).shape == (0,)     # empty id list is valid

    def test_query_rejects_bad_ids(self, dyn):
        sess = self._sess(dyn)
        with pytest.raises(ValueError, match="out of range"):
            sess.query([-1])
        with pytest.raises(ValueError, match="out of range"):
            sess.query([0, sess.n])
        with pytest.raises(ValueError, match="integers"):
            sess.query([1.5])

    def test_top_k_rejects_bad_k(self, dyn):
        sess = self._sess(dyn)
        with pytest.raises(ValueError, match="must be >= 1"):
            sess.top_k(0)
        with pytest.raises(ValueError, match="integer"):
            sess.top_k(2.5)

    def test_close_is_idempotent_and_guards_reads(self, dyn):
        sess = self._sess(dyn)
        sess.close()
        sess.close()
        assert sess.closed and sess.device_footprint == ()
        for call in (lambda: sess.query([0]), lambda: sess.top_k(1),
                     lambda: sess.update([], []),
                     lambda: sess.recompute("static"), lambda: sess.fork(),
                     lambda: sess.ranks):
            with pytest.raises(ValueError, match="closed"):
                call()
        assert sess.R is None and sess.inc is None   # buffers dropped

    def test_context_manager_closes(self, dyn):
        hg0 = dyn[0]
        with PageRankSession.from_graph(
                hg0, config=EngineConfig(engine="blocked")) as sess:
            assert sess.query([0]).shape == (1,)
        assert sess.closed

    def test_close_unregisters_from_service(self):
        graphs = [rmat(7, avg_degree=4, seed=s) for s in (0, 1)]
        svc = PageRankService(
            graphs, config=EngineConfig(engine="pallas", block_size=64),
            warmup=False)
        assert set(svc.placements()) == {0, 1}
        svc.submit(0, np.zeros((0, 2)), np.zeros((0, 2)))
        svc.sessions[0].close()
        assert svc.sessions[0] is None
        assert svc.queue == []                  # queued batches dropped
        assert set(svc.placements()) == {1}
        with pytest.raises(ValueError, match="closed"):
            svc.submit(0, np.zeros((0, 2)), np.zeros((0, 2)))
        svc.submit(1, np.zeros((0, 2)), np.zeros((0, 2)))   # slot 1 lives
        assert svc.step() == 1
        rep = svc.report()
        assert rep["sessions"][0] == {"stream": 0, "closed": True}
        assert rep["sessions"][1]["devices"]
