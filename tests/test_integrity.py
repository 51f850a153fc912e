"""Corruption fault domain: invariant scrubbing, checksummed device state
and the repair ladder (core/integrity.py + session.verify + the service
scrubber; docs/FAULTS.md §corruption).

Each injectable corruption kind must be DETECTED by the right check and
REPAIRED at the right ladder rung, with post-repair ranks matching the
accepted-batch oracle to 1e-9.  A seeded :class:`ChaosPlan` soak composes
all kinds against a serving fleet (``-m chaos``; excluded from the fast
marker path only by its own runtime, not by the slow marker).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.api import (ChaosPlan, EngineConfig, IntegrityConfig,
                       PageRankService, PageRankSession, ServingConfig)
from repro.core import pagerank as pr
from repro.core.delta import random_batch
from repro.graphs.generators import rmat

BS = 64


def _cfg(*, auto_repair=False, **over):
    base = dict(engine="pallas", block_size=BS, active_policy="rc",
                max_iterations=2000,
                integrity=IntegrityConfig(auto_repair=auto_repair))
    base.update(over)
    return EngineConfig(**base)


def _stream(sess, hg, n_batches, *, seed0=500):
    """Drive a few accepted batches, tracking the host-graph lineage."""
    cur = hg
    for i in range(n_batches):
        dels, ins = random_batch(cur, 8 / max(cur.m, 1), seed=seed0 + i)
        sess.update(dels, ins)
        cur = cur.apply_batch(dels, ins)
    return cur


def _oracle_linf(sess, cur):
    ref = pr.numpy_reference(cur.snapshot(block_size=BS), iterations=300)
    return float(pr.linf(sess.R[:cur.n], jnp.asarray(ref[:cur.n])))


@pytest.fixture(scope="module")
def graph():
    return rmat(9, avg_degree=6, seed=11)


# ---------------------------------------------------------------------------
# per-kind detection + ladder rung
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,rung", [
    ("rank", "frontier"),       # invariant violation → DF re-mark + helping
    ("tile", "rebuild"),        # tile-pool flip → operand rebuild
    ("slot", "rebuild"),        # slot-table flip → operand rebuild
    ("mirror", "rebuild"),      # mirror flip → operand rebuild
])
def test_detect_and_repair(graph, kind, rung):
    sess = PageRankSession.from_graph(graph, config=_cfg())
    cur = _stream(sess, graph, 2)
    assert sess.verify(repair=False).ok, "pre-injection state must be clean"
    sess.inject_corruption(kind, seed=3)
    rep = sess.verify(repair=True, deep=True)
    assert rep.failures, f"{kind} corruption went undetected"
    assert rep.ok, f"repair failed: {rep.failures}"
    assert rung in rep.repairs, (kind, rep.repairs)
    assert _oracle_linf(sess, cur) <= 1e-9
    integ = sess.report().integrity
    assert integ["corruption_detected"] == 1
    assert integ["repairs"][rung] >= 1
    # the state is clean again: a fresh scrub is a no-op
    assert sess.verify(repair=False).ok
    sess.close()


@pytest.mark.parametrize("kind", ["scatter_drop", "scatter_dup"])
def test_torn_scatter_detected_by_mirror_digests(graph, kind):
    """A dropped/duplicated operand scatter tears device operands away
    from the host-truth twins; the chunked mirror digests catch it and
    the rebuild rung re-derives the operands from host truth."""
    sess = PageRankSession.from_graph(graph, config=_cfg())
    cur = _stream(sess, graph, 1)
    sess.inject_corruption(kind)
    dels, ins = random_batch(cur, 8 / cur.m, seed=901)
    sess.update(dels, ins)          # the tear happens inside this update
    cur = cur.apply_batch(dels, ins)
    rep = sess.verify(repair=True, deep=False)
    assert any(f["check"] == "mirror_digest" for f in rep.failures), \
        rep.failures
    assert rep.ok and "rebuild" in rep.repairs
    assert _oracle_linf(sess, cur) <= 1e-9
    sess.close()


def test_bmat_mirror_owns_its_buffer(graph):
    """The device presence mirror is uploaded as a copy of its host twin,
    which is patched in place per batch: a write to the twin leaves the
    mirror as it was, and the mirror digests see the two disagree — at
    construction and again after the rebuild rung re-uploads it."""
    sess = PageRankSession.from_graph(graph, config=_cfg())
    for _ in range(2):
        bmat = sess.inc.aux.bmat
        mirror = np.asarray(sess._bmat).copy()
        bmat[0, -1] = not bmat[0, -1]
        np.testing.assert_array_equal(np.asarray(sess._bmat), mirror)
        rep = sess.verify(repair=True, deep=False)
        assert any(f["check"] == "mirror_digest" and f["mirror"] == "bmat"
                   for f in rep.failures), rep.failures
        assert rep.ok and "rebuild" in rep.repairs
    sess.close()


def test_graph_corruption_restores_from_store(graph, tmp_path):
    """Host-truth damage (the deep graph digest) cannot be repaired from
    the host — the ladder escalates to the checkpoint+WAL restore rung."""
    sess = PageRankSession.from_graph(
        graph, config=_cfg(durability="wal", checkpoint_interval=2),
        store_dir=str(tmp_path / "store"))
    cur = _stream(sess, graph, 3)
    sess.inject_corruption("graph", seed=7)
    rep = sess.verify(repair=True, deep=True)
    assert any(f["check"] == "graph_digest" for f in rep.failures)
    assert rep.ok and "restore" in rep.repairs
    assert _oracle_linf(sess, cur) <= 1e-9
    assert sess.report().integrity["repairs"]["restore"] >= 1
    sess.close()


def test_fused_drive_detects_and_auto_repairs(graph):
    """The zero-extra-sync path: a deferred corruption lands right before
    a batch applies, the drive's fused invariant vector flags it, and
    ``update`` climbs the ladder automatically (auto_repair=True).

    The injected kind is ``tile`` — damage to the pull matrix the driver
    actually multiplies by — because the drive cannot converge it away:
    the wrong fixed point carries a mass error the fused gate must flag.
    (A ``rank`` flip, by contrast, may legitimately self-heal when the
    vertex's chunk re-activates — the drive recomputes it from clean
    in-neighbors and there is nothing left to detect; and a ``mirror``
    flip is LATENT damage to a host-patching operand that only the
    scrubber's chunked digests can see.)"""
    sess = PageRankSession.from_graph(graph, config=_cfg(auto_repair=True))
    cur = _stream(sess, graph, 1)
    sess.inject_corruption("tile", seed=5, defer=True)
    dels, ins = random_batch(cur, 8 / cur.m, seed=911)
    sess.update(dels, ins)
    cur = cur.apply_batch(dels, ins)
    integ = sess.report().integrity
    assert integ["corruption_detected"] >= 1
    assert sum(integ["repairs"].values()) >= 1
    assert sess.verify(repair=False).ok
    assert _oracle_linf(sess, cur) <= 1e-9
    sess.close()


def test_verify_clean_is_cheap_and_counts(graph):
    sess = PageRankSession.from_graph(graph, config=_cfg())
    before = sess.report().integrity["checks_run"]
    rep = sess.verify(repair=False, deep=True)
    assert rep.ok and not rep.failures and not rep.repairs
    assert rep.checks_run > 0
    assert sess.report().integrity["checks_run"] == before + rep.checks_run
    sess.close()


# ---------------------------------------------------------------------------
# config round-trip + counters
# ---------------------------------------------------------------------------

def test_integrity_config_roundtrips_through_store(graph, tmp_path):
    icfg = IntegrityConfig(mass_tol=1e-5, scrub_interval_s=0.05,
                           auto_repair=False)
    sess = PageRankSession.from_graph(
        graph, config=_cfg(durability="wal", checkpoint_interval=1,
                           integrity=icfg),
        store_dir=str(tmp_path / "s"))
    _stream(sess, graph, 2)
    sess.save()
    sess.close()
    back = PageRankSession.restore(str(tmp_path / "s"))
    got = back.config.integrity
    assert isinstance(got, IntegrityConfig)
    assert got.mass_tol == pytest.approx(1e-5)
    assert got.scrub_interval_s == pytest.approx(0.05)
    assert got.auto_repair is False
    assert back.verify(repair=False).ok
    back.close()


def test_engine_config_coerces_integrity_dict():
    cfg = EngineConfig(engine="pallas",
                       integrity={"mass_tol": 1e-5, "auto_repair": False})
    assert isinstance(cfg.integrity, IntegrityConfig)
    assert cfg.integrity.mass_tol == pytest.approx(1e-5)
    with pytest.raises((TypeError, ValueError)):
        EngineConfig(engine="pallas", integrity={"no_such_knob": 1})


# ---------------------------------------------------------------------------
# bucket-retrace split (satellite: keep the zero-retrace bar assertable)
# ---------------------------------------------------------------------------

def test_bucket_retraces_counted_separately(graph):
    """Legitimate operand-bucket growth (the doubling ladder) compiles
    once per bucket; those compiles land in ``bucket_retraces`` and MUST
    NOT pollute ``retraces_post_warmup``, which stays the zero-retrace
    acceptance bar."""
    sess = PageRankSession.from_graph(graph, config=_cfg())
    cur = _stream(sess, graph, 2)
    # a much larger batch forces tile-pool / delta-bucket growth
    # (unique candidate pairs, deduped by key, none already present)
    rng = np.random.default_rng(77)
    cand = np.stack([rng.integers(0, cur.n, 8 * cur.m),
                     rng.integers(0, cur.n, 8 * cur.m)], 1).astype(np.int64)
    cand = cand[cand[:, 0] != cand[:, 1]]
    cand = cand[np.unique(cand[:, 0] * cur.n + cand[:, 1],
                          return_index=True)[1]]
    ins = cand[~cur.has_edges(cand)][:cur.m]
    res = sess.update(np.zeros((0, 2), np.int64), ins)
    assert res.bucket_retraces >= 0
    rep = sess.report()
    assert rep.retraces_post_warmup == 0, \
        "bucket growth leaked into the retrace bar"
    assert rep.bucket_retraces_post_warmup == res.bucket_retraces
    sess.close()


# ---------------------------------------------------------------------------
# service scrubber
# ---------------------------------------------------------------------------

def _mk_service(graph, *, serving=None, n=2, auto_repair=False):
    sessions = [PageRankSession.from_graph(
        rmat(8, avg_degree=6, seed=20 + s), config=_cfg(
            auto_repair=auto_repair))
        for s in range(n)]
    return PageRankService(
        sessions, serving=serving or ServingConfig(coalesce=False,
                                                   scrub=False))


def test_service_scrub_detects_and_repairs(graph):
    svc = _mk_service(graph)
    svc.sessions[1].inject_corruption("mirror", seed=9)
    reports = svc.scrub(deep=True, repair=True)
    assert set(reports) == {0, 1}
    assert reports[0].ok and not reports[0].failures
    assert reports[1].failures and reports[1].ok
    out = svc.report()
    assert out["integrity"]["scrubs_run"] >= 1
    assert out["integrity"]["corruption_detected"] == 1
    assert out["integrity"]["repairs"].get("rebuild", 0) >= 1
    svc.stop()


def test_background_scrubber_thread(graph):
    """With ``ServingConfig(scrub=True)`` a daemon scrubber sweeps idle
    slots at each slot's ``scrub_interval_s`` and repairs what it finds."""
    import time
    sessions = [PageRankSession.from_graph(
        rmat(8, avg_degree=6, seed=30 + s),
        config=_cfg(integrity=IntegrityConfig(auto_repair=True,
                                              scrub_interval_s=0.02)))
        for s in range(2)]
    svc = PageRankService(
        sessions, serving=ServingConfig(coalesce=False, scrub=True))
    svc.start()
    try:
        svc.sessions[0].inject_corruption("rank", seed=13)
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline:
            integ = svc.report().get("integrity", {})
            if integ.get("corruption_detected", 0) >= 1:
                break
            time.sleep(0.05)
    finally:
        svc.stop()
    integ = svc.report()["integrity"]
    assert integ["scrubs_run"] >= 1
    assert integ["corruption_detected"] >= 1
    assert sum(integ["repairs"].values()) >= 1
    assert svc.sessions[0].verify(repair=False).ok


# ---------------------------------------------------------------------------
# seeded chaos soak (composes every kind; mirrors the benchmark scenario)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_plan_soak(tmp_path):
    plan = ChaosPlan(seed=17, steps=4, streams=2,
                     require=("rank", "mirror", "graph", "scatter_drop"),
                     rate=0.0)
    counts = plan.counts()
    assert sum(counts.values()) >= 4
    cfg = _cfg(durability="wal", checkpoint_interval=2)
    sessions = [PageRankSession.from_graph(
        rmat(9, avg_degree=6, seed=40 + s), config=cfg,
        store_dir=str(tmp_path / f"slot{s}")) for s in range(2)]
    svc = PageRankService(
        sessions, serving=ServingConfig(coalesce=False, scrub=False))
    cur = {s: sessions[s].hg for s in range(2)}
    seed = iter(range(10_000))

    def advance(s):
        dels, ins = random_batch(cur[s], 8 / cur[s].m,
                                 seed=6000 + next(seed))
        svc.submit(s, dels, ins)
        cur[s] = cur[s].apply_batch(dels, ins)

    injected = detected = 0
    for step in range(plan.steps):
        for s in range(2):
            advance(s)
        svc.run_until_drained()
        for ev in plan.events_at(step):
            fault = ev.corruption()
            if fault is None:
                continue
            svc.sessions[ev.stream].inject_corruption(fault)
            injected += 1
            if fault.kind in ("scatter_drop", "scatter_dup"):
                advance(ev.stream)      # the tear needs a consuming update
        svc.run_until_drained()
        reports = svc.scrub(deep=True, repair=True)
        detected += sum(1 for r in reports.values() if r.failures)
        assert all(r.ok for r in reports.values())
    assert injected >= 4
    assert detected == injected, (detected, injected)
    # final state: clean and oracle-tight on every stream
    final = svc.scrub(deep=True, repair=False)
    assert all(r.ok and not r.failures for r in final.values())
    for s in range(2):
        ref = pr.numpy_reference(cur[s].snapshot(block_size=BS),
                                 iterations=300)
        sess = svc.sessions[s]
        assert float(pr.linf(sess.R[:sess.n],
                             jnp.asarray(ref[:sess.n]))) <= 1e-9
    svc.stop()
