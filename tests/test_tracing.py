"""The program's own measurement: host spans
(``jax.profiler.TraceAnnotation``) on the update and serving path, as a
profiler trace on the CPU records them, and device scopes
(``jax.named_scope``) in the op metadata of the fused drivers."""
import glob
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.api import (EngineConfig, IntegrityConfig, PageRankService,
                       PageRankSession, ServingConfig)
from repro.api.session import _apply_operand_delta, _seed_affected
from repro.core import pallas_engine as pe
from repro.core import push_engine as pshe
from repro.core import tiering
from repro.core.delta import random_batch
from repro.graphs.generators import rmat
from repro.kernels.block_spmv.ops import (BlockSparse, active_ladder,
                                          tile_shape)

BLOCK = 64
HOST_PLANE = "/host:CPU"
PREFIXES = ("session.", "service.")


@pytest.fixture(scope="module")
def hg():
    return rmat(8, avg_degree=5, seed=11)


def _batches(hg, k, seed0):
    out, cur = [], hg
    for i in range(k):
        dels, ins = random_batch(cur, 1e-2, seed=seed0 + i)
        out.append((dels, ins))
        cur = cur.apply_batch(dels, ins)
    return out


def _traced(log_dir, fn):
    """Run ``fn`` under the profiler; the program's spans it recorded, as
    (name, start, end, line) with ``line`` the host thread's line."""
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for k, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, k)
                    for e in line.events if e.name.startswith(PREFIXES)]
    return out


def _check_nesting(spans, parents):
    """Spans of one thread nest properly (none overlaps another in part),
    and each span named in ``parents`` lies inside one of its parents on
    the same thread."""
    for k in {ln for *_, ln in spans}:
        stack = []
        for name, s, e, _ in sorted((x for x in spans if x[3] == k),
                                    key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            assert not stack or e <= stack[-1][1], (name, stack[-1])
            stack.append((name, e))
    for name, s, e, k in spans:
        if name in parents:
            assert any(p in parents[name] and ps <= s and e <= pe_ and pk == k
                       for p, ps, pe_, pk in spans), name


SESSION_SPANS = {"session.validate", "session.plan", "session.host_graph",
                 "session.scatter", "session.seed", "session.drive"}


def _pool_bytes(hg):
    g0 = hg.snapshot(block_size=BLOCK)
    src, dst = g0.in_edges_host()
    return int(tiering.HostTilePool.from_edges(
        dst, src, g0.n_pad, g0.n_pad, block=BLOCK,
        dtype=np.dtype(np.float32)).nbytes)


@pytest.mark.parametrize("kind", ["durable", "tiered", "push"])
def test_session_update_records_its_spans(hg, tmp_path, kind):
    kw, store, extra = {}, None, set()
    if kind == "durable":
        kw, store, extra = dict(durability="wal"), str(tmp_path / "store"), \
            {"session.wal"}
    elif kind == "tiered":
        kw, extra = dict(dtype="float32",
                         device_budget_bytes=_pool_bytes(hg) // 2), \
            {"session.admit"}
    else:
        kw, extra = dict(driver="push"), {"session.push_seed"}
    sess = PageRankSession.from_graph(
        hg, config=EngineConfig(engine="pallas", block_size=BLOCK, **kw),
        store_dir=store)
    sess.warmup()
    (dels, ins), = _batches(hg, 1, seed0=3)
    spans = _traced(tmp_path / "trace", lambda: sess.update(dels, ins))
    names = {n for n, *_ in spans}
    assert names == {"session.update"} | SESSION_SPANS | extra
    assert sum(n == "session.update" for n in [x[0] for x in spans]) == 1
    parents = {n: {"session.update"} for n in SESSION_SPANS | extra}
    if kind == "push":
        # the push's host seeding lies inside the batch's seed step
        parents["session.push_seed"] = {"session.seed"}
    _check_nesting(spans, parents)
    sess.close()


def test_service_dispatch_snapshot_and_read_record_their_spans(hg,
                                                               tmp_path):
    # a read refreshes a snapshot older than 0.5 s (budget x 0.5)
    serving = ServingConfig(staleness_budget_s=1.0, retry_backoff_s=1e-3)
    svc = PageRankService(
        [hg], config=EngineConfig(engine="pallas", block_size=BLOCK,
                                  integrity=IntegrityConfig()),
        serving=serving)
    sess = svc.sessions[0]
    orig, calls = sess.update, {"n": 0}

    def flaky_update(d, i, **kw):        # one transient failure
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient device hiccup")
        return orig(d, i, **kw)

    sess.update = flaky_update
    dead = PageRankService([hg], config=EngineConfig(engine="pallas",
                                                     block_size=BLOCK))
    dead.inject_session_fault(0, after_dispatches=0, kind="dead")
    bs = _batches(hg, 3, seed0=7)

    def serve():
        for dels, ins in bs[:2]:
            svc.submit(0, dels, ins)
        svc.step()                       # one coalesced dispatch, retried
        svc.query(0, [1, 2, 3])
        time.sleep(0.6)                  # past the refresh point
        svc.top_k(0, 5)                  # refreshes the snapshot itself
        svc.scrub()
        dead.submit(0, *bs[2])
        dead.step()                      # the slot dies; no store: shed

    spans = _traced(tmp_path / "trace", serve)
    names = [n for n, *_ in spans]
    assert set(names) == {"service.dispatch", "service.coalesce",
                          "service.retry_backoff", "service.snapshot",
                          "service.read", "service.scrub",
                          "service.failover", "session.update"} | \
        SESSION_SPANS
    assert names.count("service.read") == 2
    assert names.count("service.dispatch") == 2
    assert names.count("service.snapshot") == 2
    _check_nesting(spans, {
        "service.coalesce": {"service.dispatch"},
        "service.retry_backoff": {"service.dispatch"},
        "session.update": {"service.dispatch"},
        "service.snapshot": {"service.dispatch", "service.read"},
        **{n: {"session.update"} for n in SESSION_SPANS}})
    # one snapshot refresh after the dispatch, one inside the late read
    reads = [(s, e) for n, s, e, _ in spans if n == "service.read"]
    snaps = [(s, e) for n, s, e, _ in spans if n == "service.snapshot"]
    assert sum(rs <= s and e <= re for s, e in snaps
               for rs, re in reads) == 1


# ---------------------------------------------------------------------------
# device scopes: op_name metadata of the traced programs
# ---------------------------------------------------------------------------

N_RB, MAX_TILES, TILE_CAP = 32, 4, 256
N_PAD = N_RB * BLOCK
MAX_ITERATIONS = 20


def _mat(dt=jnp.float32):
    S = jax.ShapeDtypeStruct
    return BlockSparse(n_rows=N_PAD, n_cols=N_PAD, block=BLOCK,
                       max_tiles=MAX_TILES,
                       tiles=S((TILE_CAP,) + tile_shape(BLOCK), dt),
                       tile_cols=S((N_RB, MAX_TILES), jnp.int32),
                       tile_idx=S((N_RB * MAX_TILES,), jnp.int32))


def _lowered(program):
    S = jax.ShapeDtypeStruct
    f32, b, i32 = jnp.float32, jnp.bool_, jnp.int32
    v = lambda dt: S((N_PAD,), dt)      # noqa: E731
    rb = lambda dt: S((N_RB,), dt)      # noqa: E731
    bmat = S((N_RB, N_RB), b)
    common = dict(interpret=True, backend="xla")
    if program == "pull":
        return pe._driver.lower(
            _mat(), v(f32), v(b), v(b), v(i32), rb(i32), rb(i32), bmat,
            rb(b), S((), f32), S((), f32), S((), f32),
            S((MAX_ITERATIONS, 1), b), S((MAX_ITERATIONS, 1), b),
            S((MAX_ITERATIONS, 1), f32), S((MAX_ITERATIONS,), b),
            n=N_PAD, block_size=BLOCK, mode="lf", expand=True,
            active_policy="affected", max_iterations=MAX_ITERATIONS,
            **common)
    if program == "push":
        return pshe._push_driver.lower(
            _mat(), v(f32), v(f32), v(b), v(i32), rb(i32), bmat, rb(b),
            S((), f32), S((), f32), n=N_PAD, block_size=BLOCK,
            max_iterations=MAX_ITERATIONS, **common)
    if program == "seed":
        return _seed_affected.lower(
            _mat(), _mat(), bmat, S((8, 2), i32), v(b),
            block_size=BLOCK, **common)
    return _apply_operand_delta.lower(
        v(i32), rb(i32), rb(i32), bmat, S((8,), i32), S((8,), i32),
        S((8,), i32), block=BLOCK)


@pytest.mark.parametrize("program,scopes", [
    ("pull", ["df.sweep", "df.expand", "df.account", "spmv.sum.k",
              "spmv.or.k"]),
    ("push", ["df.sweep", "df.expand", "df.account", "df.sweep/spmv.push/",
              "spmv.sum.k"]),
    ("seed", ["df.seed", "spmv.or.k"]),
    ("scatter", ["delta.scatter"]),
])
def test_device_scopes_name_the_ops(program, scopes):
    assert len(active_ladder(N_RB)) > 1
    text = _lowered(program).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    if program in ("pull", "seed"):
        # every bucket of the ladder is a branch under its own scope
        for K in active_ladder(N_RB):
            assert f"spmv.or.k{K}/" in text, K
