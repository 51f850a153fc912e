"""Overload, degraded-read and chaos-under-load behavior of
:class:`repro.api.PageRankService` (docs/FAULTS.md "session" domain;
docs/API.md serving lifecycle).

Covers the serving-policy axis end to end: admission control sheds with
machine-readable reasons instead of growing queues without bound; deadlines
expire queued work and count late completions; transient dispatch failures
retry with backoff; reads are served degraded from bounded-staleness
snapshots (and survive an in-flight update or a dead slot); malformed
batches are rejected before any device scatter or WAL append; and a slot
killed or stalled mid-load is failed over by the watchdog with its queue
drained to the respawn, converging to oracle parity.
"""
import os
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro.api import (AdmissionRejected, EngineConfig, PageRankService,
                       PageRankSession, ServingConfig, SweepCapWarning)
from repro.core import pagerank as pr
from repro.core.delta import random_batch
from repro.graphs.generators import rmat

BLOCK = 64


def _cfg(**kw):
    return EngineConfig(engine="pallas", block_size=BLOCK, **kw)


def _batches(hg, k, seed0=0):
    """k sequential random batches + the graph after each prefix."""
    out, cur = [], hg
    for i in range(k):
        d, ins = random_batch(cur, 1e-2, seed=seed0 + i)
        out.append((d, ins))
        cur = cur.apply_batch(d, ins)
    return out, cur


@pytest.fixture(scope="module")
def hg():
    return rmat(8, avg_degree=5, seed=11)


# ---------------------------------------------------------------------------
# admission control + shedding
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_reject_policy_raises_with_machine_readable_reason(self, hg):
        svc = PageRankService(
            [hg], config=_cfg(), warmup=False,
            serving=ServingConfig(max_queue_depth=2))
        bs, _ = _batches(hg, 3)
        for d, ins in bs[:2]:
            svc.submit(0, d, ins)
        with pytest.raises(AdmissionRejected) as ei:
            svc.submit(0, *bs[2])
        reason = ei.value.reason
        assert reason["code"] == "queue_full"
        assert reason["stream"] == 0
        assert reason["queue_depth"] == 2
        assert reason["max_queue_depth"] == 2
        assert reason["shed_policy"] == "reject"
        # the queue did NOT grow past its bound, and the shed is recorded
        assert len(svc.queue) == 2
        rep = svc.report()
        assert rep["requests_shed"] == 1
        assert rep["shed_reasons"] == {"queue_full": 1}

    def test_drop_oldest_policy_sheds_head_keeps_newest(self, hg):
        svc = PageRankService(
            [hg], config=_cfg(), warmup=False,
            serving=ServingConfig(max_queue_depth=2,
                                  shed_policy="drop_oldest"))
        bs, _ = _batches(hg, 3)
        uids = [svc.submit(0, d, ins) for d, ins in bs]   # no raise
        assert [r.uid for r in svc.queue] == uids[1:]     # oldest shed
        shed = svc.shed_requests[0]
        assert shed.uid == uids[0]
        assert shed.shed_reason["code"] == "queue_full_dropped_oldest"
        rep = svc.report()
        assert rep["shed_reasons"] == {"queue_full_dropped_oldest": 1}


# ---------------------------------------------------------------------------
# deadlines + retries
# ---------------------------------------------------------------------------

class TestDeadlines:
    def test_expired_queued_request_is_shed_before_dispatch(self, hg):
        svc = PageRankService([hg], config=_cfg(), warmup=False)
        bs, _ = _batches(hg, 1)
        uid = svc.submit(0, *bs[0], deadline_s=1e-4)
        time.sleep(0.01)
        assert svc.step() == 0          # never dispatched
        assert svc.sessions[0].report().n_updates == 0
        shed = svc.shed_requests[0]
        assert shed.uid == uid
        assert shed.shed_reason["code"] == "deadline_expired"
        rep = svc.report()
        assert rep["deadline_misses"] == 1
        assert rep["requests_shed"] == 1

    def test_late_completion_counts_as_deadline_miss(self, hg):
        svc = PageRankService([hg], config=_cfg())
        sess = svc.sessions[0]
        orig = sess.update

        def slow_update(d, i, **kw):
            time.sleep(0.08)
            return orig(d, i, **kw)

        sess.update = slow_update
        bs, _ = _batches(hg, 1)
        svc.submit(0, *bs[0], deadline_s=0.03)
        svc.run_until_drained()
        req = svc.finished[0]
        assert req.done and req.deadline_missed
        assert svc.report()["deadline_misses"] == 1

    def test_transient_failure_retries_with_backoff(self, hg):
        svc = PageRankService(
            [hg], config=_cfg(),
            serving=ServingConfig(max_retries=2, retry_backoff_s=1e-3))
        sess = svc.sessions[0]
        orig, calls = sess.update, {"n": 0}

        def flaky_update(d, i, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient device hiccup")
            return orig(d, i, **kw)

        sess.update = flaky_update
        bs, cur = _batches(hg, 1)
        svc.submit(0, *bs[0])
        done = svc.run_until_drained()
        assert len(done) == 1 and done[0].done
        assert done[0].attempts == 2
        assert svc.report()["retries"] == 1
        ref = pr.numpy_reference(cur.snapshot(block_size=BLOCK),
                                 iterations=300)
        assert pr.linf(sess.R[:cur.n], jnp.asarray(ref[:cur.n])) < 1e-8

    def test_compile_error_is_raised_not_retried(self, hg):
        """A program that does not lower or compile is not a transient
        fault: the dispatch raises it at once, with no retry and no dead
        slot to hide it behind."""
        svc = PageRankService(
            [hg], config=_cfg(),
            serving=ServingConfig(max_retries=2, retry_backoff_s=1e-3))
        sess = svc.sessions[0]
        calls = {"n": 0}

        def broken_update(d, i, **kw):
            calls["n"] += 1
            raise NotImplementedError("float64")

        sess.update = broken_update
        bs, _ = _batches(hg, 1)
        svc.submit(0, *bs[0])
        with pytest.raises(NotImplementedError, match="float64"):
            svc.run_until_drained()
        assert calls["n"] == 1
        rep = svc.report()
        assert rep["retries"] == 0


# ---------------------------------------------------------------------------
# degraded-mode reads
# ---------------------------------------------------------------------------

class TestDegradedReads:
    def test_reads_report_bounded_staleness(self, hg):
        svc = PageRankService(
            [hg], config=_cfg(),
            serving=ServingConfig(staleness_budget_s=10.0))
        bs, _ = _batches(hg, 2)
        for d, ins in bs:
            svc.submit(0, d, ins)
        svc.run_until_drained()
        res = svc.query(0, [0, 1, 2])
        assert res.degraded
        assert res.staleness_s >= 0.0
        assert res.lag_updates == 0     # snapshot refreshed after dispatch
        assert np.asarray(res).shape == (3,)
        # snapshot values match the live session exactly (shared arrays)
        np.testing.assert_array_equal(
            np.asarray(res), np.asarray(svc.sessions[0].query([0, 1, 2])))
        vals, verts = svc.top_k(0, 4)   # tuple-unpacks like the session
        assert vals.shape == (4,) and verts.shape == (4,)
        q = svc.report()["queries"]
        assert q["served"] == 2
        assert q["staleness_max_s"] >= 0.0

    def test_stale_snapshot_refreshes_when_idle(self, hg):
        svc = PageRankService(
            [hg], config=_cfg(),
            serving=ServingConfig(staleness_budget_s=0.01))
        bs, _ = _batches(hg, 1)
        svc.submit(0, *bs[0])
        svc.run_until_drained()
        time.sleep(0.05)                # snapshot goes stale past budget
        res = svc.query(0, [0])
        assert res.staleness_s <= 0.05  # refreshed at read time
        assert res.lag_updates == 0

    def test_reads_survive_slot_death(self, hg):
        svc = PageRankService([hg], config=_cfg(), warmup=False,
                              serving=ServingConfig(watchdog=False))
        before = np.asarray(svc.query(0, [0, 1]))
        sess = svc.sessions[0]
        sess._service = None            # crash-stop, not a clean close
        sess.close()
        res = svc.query(0, [0, 1])      # still served, from the snapshot
        assert res.degraded
        np.testing.assert_array_equal(np.asarray(res), before)

    def test_disabled_degraded_reads_serve_live(self, hg):
        svc = PageRankService(
            [hg], config=_cfg(), warmup=False,
            serving=ServingConfig(degraded_reads=False))
        res = svc.query(0, [0])
        assert not res.degraded
        assert res.staleness_s == 0.0


# ---------------------------------------------------------------------------
# input validation before scatter / WAL
# ---------------------------------------------------------------------------

class TestInputValidation:
    BAD = [
        (np.array([[0, np.nan]]), "non-finite"),
        (np.array([[0, np.inf]]), "non-finite"),
        (np.array([[0.5, 1.0]]), "non-integral"),
        (np.array([[0, 10 ** 6]]), "out-of-range"),
        (np.array([[-1, 2]]), "out-of-range"),
        (np.array([[1, 2], [1, 2]]), "duplicate"),
        (np.array([[1, 2, 3]]), "edge pairs"),
        (np.array([["a", "b"]], dtype=object), "object"),
    ]

    @pytest.mark.parametrize("bad,msg", BAD)
    def test_session_update_rejects_malformed(self, hg, bad, msg):
        sess = PageRankSession.from_graph(hg, config=_cfg())
        with pytest.raises(ValueError, match=msg):
            sess.update(np.zeros((0, 2)), bad)
        assert sess.report().n_updates == 0     # nothing applied

    def test_self_loop_and_del_ins_overlap_rejected(self, hg):
        sess = PageRankSession.from_graph(hg, config=_cfg())
        with pytest.raises(ValueError, match="self-loop"):
            sess.update(np.zeros((0, 2)), np.array([[3, 3]]))
        with pytest.raises(ValueError, match="both deletions"):
            sess.update(np.array([[1, 2]]), np.array([[1, 2]]))

    def test_service_rejects_at_admission_not_in_queue(self, hg):
        svc = PageRankService([hg], config=_cfg(), warmup=False)
        with pytest.raises(ValueError, match="non-finite"):
            svc.submit(0, np.zeros((0, 2)), np.array([[0, np.nan]]))
        assert svc.queue == []          # never admitted

    def test_bad_batch_never_reaches_wal(self, hg, tmp_path):
        store = str(tmp_path / "s0")
        sess = PageRankSession.from_graph(
            hg, config=_cfg(durability="wal"), store_dir=store)
        good, cur = _batches(hg, 1, seed0=33)
        sess.update(*good[0])
        with pytest.raises(ValueError, match="out-of-range"):
            sess.update(np.zeros((0, 2)), np.array([[0, 10 ** 6]]))
        sess.close()
        # the restore replays exactly the one good batch — the rejected
        # batch left no WAL record to poison the replay
        twin = PageRankSession.restore(store)
        assert twin._batch_index == 1
        ref = pr.numpy_reference(cur.snapshot(block_size=BLOCK),
                                 iterations=300)
        assert pr.linf(twin.ranks[:cur.n], jnp.asarray(ref[:cur.n])) < 1e-8


# ---------------------------------------------------------------------------
# sweep-cap surfacing (no more silent capping)
# ---------------------------------------------------------------------------

class TestSweepCap:
    def test_capped_update_warns_and_reports(self, hg):
        sess = PageRankSession.from_graph(hg, config=_cfg(max_iterations=1))
        bs, _ = _batches(hg, 1, seed0=70)
        with pytest.warns(SweepCapWarning, match="max_iterations"):
            res = sess.update(*bs[0])
        assert not res.converged
        rep = sess.report()
        assert rep.sweep_cap_hits == 1
        assert rep.batches_converged == 0

    def test_converged_update_does_not_warn(self, hg):
        import warnings
        sess = PageRankSession.from_graph(hg, config=_cfg())
        bs, _ = _batches(hg, 1, seed0=71)
        with warnings.catch_warnings():
            warnings.simplefilter("error", SweepCapWarning)
            res = sess.update(*bs[0])
        assert res.converged
        rep = sess.report()
        assert rep.sweep_cap_hits == 0 and rep.batches_converged == 1

    def test_run_stream_aggregates_convergence(self, hg):
        from repro.core.stream import run_stream
        bs, _ = _batches(hg, 3, seed0=72)
        rep = run_stream(hg, bs, block_size=BLOCK)
        assert rep.batches_converged == 3
        assert rep.sweep_cap_hits == 0 and rep.all_converged


# ---------------------------------------------------------------------------
# chaos under load: watchdog failover drains the queue to the respawn
# ---------------------------------------------------------------------------

class TestFailoverUnderLoad:
    def _durable(self, hg, tmp_path, name):
        return PageRankSession.from_graph(
            hg, config=_cfg(durability="wal", checkpoint_interval=2),
            store_dir=str(tmp_path / name))

    def test_dead_slot_drains_to_respawn_sync(self, hg, tmp_path):
        svc = PageRankService([self._durable(hg, tmp_path, "dead")])
        svc.inject_session_fault(0, after_dispatches=1, kind="dead")
        bs, cur = _batches(hg, 3, seed0=50)
        for d, ins in bs:               # interleave so the fault fires
            svc.submit(0, d, ins)
            svc.step()
        done = svc.run_until_drained()
        assert len(done) == 3 and all(r.done for r in done)
        rep = svc.report()
        events = rep["watchdog"]
        assert len(events) == 1
        assert events[0]["kind"] == "dead"
        assert events[0]["domain"] == "session"
        assert events[0]["drained_requests"] >= 1
        # two records on the respawned session: the process-domain restore
        # itself + the session-domain watchdog drain
        assert rep["sessions"][0]["recoveries"] == 2
        ref = pr.numpy_reference(cur.snapshot(block_size=BLOCK),
                                 iterations=300)
        assert pr.linf(svc.sessions[0].ranks[:cur.n],
                       jnp.asarray(ref[:cur.n])) < 1e-8

    def test_stuck_slot_fails_over_under_background_load(self, hg,
                                                         tmp_path):
        svc = PageRankService(
            [self._durable(hg, tmp_path, "stuck")],
            serving=ServingConfig(heartbeat_timeout_s=1.0))
        svc.inject_session_fault(0, after_dispatches=1, kind="stuck",
                                 stall_s=6.0)
        svc.start()
        try:
            bs, cur = _batches(hg, 4, seed0=60)
            for d, ins in bs:
                svc.submit(0, d, ins)
                time.sleep(0.15)
        finally:
            svc.stop()
        rep = svc.report()
        assert rep["requests_done"] == 4
        assert rep["requests_queued"] == 0
        events = rep["watchdog"]
        assert events and events[0]["kind"] == "stuck"
        assert events[0]["drained_requests"] >= 1
        ref = pr.numpy_reference(cur.snapshot(block_size=BLOCK),
                                 iterations=300)
        assert pr.linf(svc.sessions[0].ranks[:cur.n],
                       jnp.asarray(ref[:cur.n])) < 1e-8

    def test_failover_drain_orders_stranded_before_midrecovery_submits(
            self, hg, tmp_path):
        # A durable dead slot keeps accepting submits while the respawn is
        # restoring.  Those land in the (cleared) queue before the drain
        # re-queues the stranded pre-kill batches, so the drain must
        # PREPEND the stranded run: delta batches are order-sensitive, and
        # stranded delete(e) + mid-recovery insert(e) nets to insert (edge
        # survives) only in submit order — the inverted order nets to a
        # delete, silently diverging the served ranks from the
        # accepted-batch lineage.
        svc = PageRankService([self._durable(hg, tmp_path, "order")])
        svc.inject_session_fault(0, after_dispatches=0, kind="dead")
        e = hg.edges[:1]                    # one existing edge
        none = np.zeros((0, 2), np.int64)
        orig_failover = svc.failover

        def failover_then_submit(stream, **kw):
            out = orig_failover(stream, **kw)
            svc.submit(0, none, e)          # re-insert e mid-recovery
            return out

        svc.failover = failover_then_submit
        svc.submit(0, e, none)              # delete e (stranded by kill)
        svc.step()              # dispatch dies; watchdog drains + respawns
        done = svc.run_until_drained()
        assert len(done) == 2 and all(r.done for r in done)
        # submit order [delete(e), insert(e)] nets to e present
        assert svc.sessions[0].hg.has_edges(e).all()
        ref = pr.numpy_reference(hg.snapshot(block_size=BLOCK),
                                 iterations=300)
        assert pr.linf(svc.sessions[0].ranks[:hg.n],
                       jnp.asarray(ref[:hg.n])) < 1e-8

    def test_dead_slot_without_store_sheds_with_reason(self, hg):
        svc = PageRankService([hg], config=_cfg())    # no durability
        svc.inject_session_fault(0, after_dispatches=0, kind="dead")
        bs, _ = _batches(hg, 2, seed0=65)
        for d, ins in bs:
            svc.submit(0, d, ins)
        svc.run_until_drained(max_ticks=20)
        rep = svc.report()
        assert rep["requests_done"] == 0
        assert rep["requests_shed"] == 2
        assert rep["shed_reasons"] == {"slot_dead": 2}
        assert rep["watchdog"] and \
            "no store" in rep["watchdog"][0]["description"]
