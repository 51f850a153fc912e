"""Benchmark orchestrator: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure (DESIGN.md §6) plus the roofline report.
``--quick`` trims graph counts/sweep points for CI-speed runs; the default
is the full container-scale suite.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time
import traceback


SMOKE_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_smoke.json")


STREAM_SIZES = (12, 14)         # log2 vertex counts for the stream scenario
STREAM_BATCHES = 6              # delta batches per stream
STREAM_BATCH_EDGES = 8          # fixed batch size (edges) across sizes

SERVICE_SESSIONS = 3            # concurrent sessions in the service scenario
SERVICE_BATCHES = 4             # update batches submitted per session
SERVICE_BATCH_EDGES = 8         # edges per batch
SERVICE_QUERY_CLIENTS = 3       # concurrent readers during the drain
SERVICE_QUERIES_PER_CLIENT = 6  # reads each client issues

SERVE_LOAD_STREAMS = 2          # durable update streams under overload
SERVE_LOAD_LOG2_N = 10          # graph size per stream
SERVE_LOAD_QUEUE_DEPTH = 4      # admission-control bound per stream
SERVE_LOAD_BURSTS = 24          # submit bursts per stream
SERVE_LOAD_BURST = 8            # submits per burst = 2x the queue bound
SERVE_LOAD_BURST_GAP_S = 0.25   # gap between bursts (dispatches interleave)
SERVE_LOAD_CLIENTS = 24         # concurrent query clients
SERVE_LOAD_READS = 15           # reads per client (~360 reads total)
SERVE_LOAD_KILL_AFTER = 2       # dispatches before stream 0 is killed

CHAOS_STREAMS = 2               # durable streams under the chaos soak
CHAOS_STEPS = 8                 # soak steps (one update round + scrub each)
CHAOS_LOG2_N = 10               # graph size per stream
CHAOS_BATCH_EDGES = 8           # edges per update batch
CHAOS_SEED = 93                 # ChaosPlan seed: same seed, same schedule
CHAOS_RATE = 0.25               # extra seeded events beyond the required set
CHAOS_REQUIRE = ("rank", "tile", "slot", "mirror", "graph",
                 "scatter_drop", "scatter_dup", "slot_dead")

SHARDED_DEVICES = 8             # forced host devices for the sharded scenario
SHARDED_BATCHES = 6             # DF batches per partitioner
SHARDED_LOG2_N = 10             # graph size (subprocess recompiles per part.)

RECOVERY_LOG2_N = 10            # graph size for the kill+restore scenario
RECOVERY_KILL_AFTER = 4         # durable batches applied before SIGKILL
RECOVERY_AFTER = 2              # batches served post-restore

PPR_N = 512                     # vertices in the walk-engine scenario
PPR_AVG_DEG = 6                 # powerlaw generator target degree
PPR_R_CURVE = (4, 16, 64)       # walks/vertex sweep (accuracy vs R)
PPR_L = 64                      # walk-length cap
PPR_SEED_SETS = 8               # seed sets averaged into each L1 point
PPR_SEEDS_PER_SET = 3           # |S| per personalized query
PPR_BATCHES = 6                 # delta batches for the localization record
PPR_BATCH_EDGES = 8             # edges per delta batch
PPR_USERS = 1000                # simulated personalized-query users
PPR_TOP_K = 10                  # ranking depth per user query


def _smoke_service() -> dict:
    """Multi-session serving scenario: N concurrent dynamic streams behind
    per-stream queues (``repro.api.PageRankService``, the serve-engine slot
    design), with concurrent query clients reading degraded-mode (from the
    per-slot snapshots) while the queues drain.  Records per-session
    p50/p95 update latency and retrace counts, the service-level request
    latency (queue wait included), and the query p50/p95 + staleness
    bound.  Sessions share the jit caches, so post-warmup retraces must
    stay 0 across **all** sessions — the multi-tenant streaming acceptance
    signal.  ``coalesce=False`` keeps one dispatch per submitted batch so
    the per-request latency series stays comparable across runs (the
    coalescing dispatcher is exercised by ``serve_load``)."""
    import threading

    import jax.numpy as jnp
    from repro.api import EngineConfig, PageRankService, ServingConfig
    from repro.core import pagerank as pr
    from repro.core.delta import random_batch
    from repro.graphs.generators import kmer_chains

    graphs = [kmer_chains(1 << 12, seed=30 + s)
              for s in range(SERVICE_SESSIONS)]
    svc = PageRankService(
        graphs,
        config=EngineConfig(engine="pallas", block_size=64,
                            active_policy="rc"),
        serving=ServingConfig(coalesce=False))
    cur = list(graphs)
    for j in range(SERVICE_BATCHES):
        for i in range(len(cur)):
            dels, ins = random_batch(cur[i], SERVICE_BATCH_EDGES / cur[i].m,
                                     seed=500 + 10 * i + j)
            svc.submit(i, dels, ins)
            cur[i] = cur[i].apply_batch(dels, ins)

    def _client(cid: int) -> None:
        for r in range(SERVICE_QUERIES_PER_CLIENT):
            s = (cid + r) % SERVICE_SESSIONS
            if r % 2 == 0:
                svc.query(s, [0, 1, 2, 3])
            else:
                svc.top_k(s, 5)

    readers = [threading.Thread(target=_client, args=(c,))
               for c in range(SERVICE_QUERY_CLIENTS)]
    for t in readers:
        t.start()
    svc.run_until_drained()        # updates drain while the readers read
    for t in readers:
        t.join()
    out = svc.report()
    out["batches_per_session"] = SERVICE_BATCHES
    # parity: every session's served ranks vs the independent oracle on its
    # final graph
    errs = []
    for i, hg in enumerate(cur):
        ref = pr.numpy_reference(hg.snapshot(block_size=64), iterations=300)
        n = svc.sessions[i].n
        errs.append(float(pr.linf(svc.sessions[i].R[:n],
                                  jnp.asarray(ref[:n]))))
    out["linf_vs_reference_max"] = max(errs)
    return out


def _smoke_serve_load() -> dict:
    """Overload + chaos serving scenario (the PR-6 acceptance scenario):
    durable update streams driven at ~2x their admission-control bound by
    burst submitters, hundreds of concurrent degraded-mode reads, and a
    slot killed mid-load so the watchdog must fail it over and drain its
    queue to the respawn.  Records queue-wait vs per-batch compute
    percentiles (continuous dispatch bounds wait by ONE in-flight
    dispatch — never the stacked multi-dispatch waits of the old
    per-tick barrier),
    shed/deadline/retry counters (bounded queues shed instead of growing),
    query latency + staleness bounds, the watchdog event log, and oracle
    parity of every surviving slot against the accepted-batch lineage."""
    import tempfile
    import threading

    import jax.numpy as jnp
    from repro.api import (AdmissionRejected, EngineConfig, PageRankService,
                           PageRankSession, ServingConfig)
    from repro.core import pagerank as pr
    from repro.core.delta import random_batch
    from repro.graphs.generators import kmer_chains

    store_root = tempfile.mkdtemp(prefix="repro-serve-load-")
    # max_iterations=2000: the post-failover drain dispatch coalesces
    # several bursts into one batch and reconverges from the restored
    # checkpoint+WAL state, which can legitimately need more than the
    # 500-sweep default at tau=1e-10 — give it headroom rather than
    # serving a capped iterate in the acceptance scenario
    cfg = EngineConfig(engine="pallas", block_size=64, active_policy="rc",
                       durability="wal", checkpoint_interval=4,
                       max_iterations=2000)
    sessions = [
        PageRankSession.from_graph(
            kmer_chains(1 << SERVE_LOAD_LOG2_N, seed=80 + s), config=cfg,
            store_dir=os.path.join(store_root, f"slot{s}"))
        for s in range(SERVE_LOAD_STREAMS)]
    svc = PageRankService(
        sessions,
        serving=ServingConfig(max_queue_depth=SERVE_LOAD_QUEUE_DEPTH,
                              shed_policy="reject", deadline_s=30.0,
                              staleness_budget_s=0.25,
                              heartbeat_timeout_s=15.0))
    svc.inject_session_fault(0, after_dispatches=SERVE_LOAD_KILL_AFTER,
                             kind="dead")

    # accepted-batch lineage per stream: `cur` advances only on admitted
    # submits, so the end state is the oracle for whatever survived
    # shedding — robust to which particular submits get rejected
    cur = [s.hg for s in sessions]
    submitted = [0] * SERVE_LOAD_STREAMS
    shed_local = [0] * SERVE_LOAD_STREAMS

    def _submitter(s: int) -> None:
        for b in range(SERVE_LOAD_BURSTS):
            for k in range(SERVE_LOAD_BURST):   # 2x the queue bound, fast
                dels, ins = random_batch(
                    cur[s], SERVICE_BATCH_EDGES / cur[s].m,
                    seed=9000 + 100 * s + 10 * b + k)
                try:
                    svc.submit(s, dels, ins)
                except AdmissionRejected:
                    shed_local[s] += 1
                    continue
                submitted[s] += 1
                cur[s] = cur[s].apply_batch(dels, ins)
            time.sleep(SERVE_LOAD_BURST_GAP_S)

    def _client(cid: int) -> None:
        for r in range(SERVE_LOAD_READS):
            s = (cid + r) % SERVE_LOAD_STREAMS
            if r % 3 == 0:
                svc.top_k(s, 5)
            else:
                svc.query(s, [(cid + 7 * r) % sessions[s].n])

    with svc:                       # background dispatch + watchdog
        writers = [threading.Thread(target=_submitter, args=(s,))
                   for s in range(SERVE_LOAD_STREAMS)]
        readers = [threading.Thread(target=_client, args=(c,))
                   for c in range(SERVE_LOAD_CLIENTS)]
        for t in writers + readers:
            t.start()
        for t in writers + readers:
            t.join()
        svc.run_until_drained()
    out = svc.report()
    out["offered_per_stream"] = SERVE_LOAD_BURSTS * SERVE_LOAD_BURST
    out["accepted_per_stream"] = list(submitted)
    out["overload_factor"] = round(
        SERVE_LOAD_BURST / SERVE_LOAD_QUEUE_DEPTH, 2)
    out["deadline_miss_rate"] = round(
        out["deadline_misses"] / max(out["requests_done"], 1), 4)
    # the acceptance ratio: with coalescing, a queued request waits at most
    # the ONE in-flight dispatch (ratio ~<=1 even at 2x overload — an
    # instantaneous burst lands right as a dispatch starts, so its wait is
    # that dispatch's full wall time), where the old per-tick barrier
    # design stacked waits several dispatches deep (ratio >> 1)
    out["queue_wait_over_compute_p50"] = round(
        out["queue_wait_p50_ms"] / max(out["exec_p50_ms"], 1e-9), 3)
    errs = []
    for s in range(SERVE_LOAD_STREAMS):
        ref = pr.numpy_reference(cur[s].snapshot(block_size=64),
                                 iterations=300)
        sess = svc.sessions[s]
        errs.append(float(pr.linf(sess.ranks[:sess.n],
                                  jnp.asarray(ref[:sess.n]))))
    out["linf_vs_reference_max"] = max(errs)
    return out


def _smoke_chaos() -> dict:
    """Silent-corruption chaos scenario (the PR-7 acceptance scenario):
    durable streams under a seeded :class:`~repro.core.chaos.ChaosPlan`
    composing every corruption kind (rank/tile/slot-table/mirror bit
    flips, dropped + duplicated operand scatters, host-graph corruption)
    with a session-domain slot kill, on a reproducible schedule.  Each
    soak step applies one update round, injects that step's scheduled
    faults through the public surfaces, and runs one synchronous
    deterministic scrub (``svc.scrub(deep=True, repair=True)``) so every
    detection is attributable to exactly one injection.  Gates: every
    injected corruption detected, at least one repair at every ladder
    rung (frontier / rebuild / restore), a clean final scrub, and oracle
    parity of the accepted-batch lineage on every stream."""
    import tempfile

    import jax.numpy as jnp
    from repro.api import (EngineConfig, IntegrityConfig, PageRankService,
                           PageRankSession, ServingConfig)
    from repro.core import pagerank as pr
    from repro.core.chaos import ChaosPlan
    from repro.core.delta import random_batch
    from repro.graphs.generators import kmer_chains

    plan = ChaosPlan(seed=CHAOS_SEED, steps=CHAOS_STEPS,
                     streams=CHAOS_STREAMS, require=CHAOS_REQUIRE,
                     rate=CHAOS_RATE)
    store_root = tempfile.mkdtemp(prefix="repro-chaos-")
    # auto_repair=False: updates only *flag* (fused invariants), the
    # harness's explicit scrub both detects and repairs — keeping the
    # injected→detected accounting exactly 1:1.  max_iterations headroom
    # for the post-restore re-converge, as in serve_load.
    cfg = EngineConfig(engine="pallas", block_size=64, active_policy="rc",
                       durability="wal", checkpoint_interval=4,
                       max_iterations=2000,
                       integrity=IntegrityConfig(auto_repair=False))
    sessions = [
        PageRankSession.from_graph(
            kmer_chains(1 << CHAOS_LOG2_N, seed=140 + s), config=cfg,
            store_dir=os.path.join(store_root, f"slot{s}"))
        for s in range(CHAOS_STREAMS)]
    svc = PageRankService(sessions, serving=ServingConfig(coalesce=False))

    # accepted-batch lineage per stream = the parity oracle at the end
    cur = [s.hg for s in sessions]
    seed_ctr = iter(range(100_000))

    def _advance(s: int) -> None:
        dels, ins = random_batch(cur[s], CHAOS_BATCH_EDGES / cur[s].m,
                                 seed=7000 + next(seed_ctr))
        svc.submit(s, dels, ins)
        cur[s] = cur[s].apply_batch(dels, ins)

    injected = detected = repaired_clean = 0
    repairs_by_rung: dict = {}
    detect_lat = []
    for step in range(plan.steps):
        for s in range(CHAOS_STREAMS):
            _advance(s)
        svc.run_until_drained()
        t_inject = {}
        for ev in plan.events_at(step):
            if ev.session_fault() is not None:
                # session-domain composition: the next dispatch kills the
                # slot; the synchronous watchdog poll fails it over from
                # its durable store and drains the queue to the respawn
                svc.inject_session_fault(ev.stream, kind="dead")
                _advance(ev.stream)
                continue
            svc.sessions[ev.stream].inject_corruption(ev.corruption())
            t_inject[ev.stream] = time.perf_counter()
            injected += 1
            if ev.kind.startswith("scatter"):
                _advance(ev.stream)   # scatter faults tear the NEXT update
        svc.run_until_drained()
        for s, rep in svc.scrub(deep=True, repair=True).items():
            if not rep.failures:
                continue
            detected += 1
            if s in t_inject:
                detect_lat.append(time.perf_counter() - t_inject.pop(s))
            for rung in rep.repairs:
                repairs_by_rung[rung] = repairs_by_rung.get(rung, 0) + 1
            repaired_clean += int(rep.ok)
    final = svc.scrub(deep=True, repair=True)
    out = svc.report()
    errs = []
    for s in range(CHAOS_STREAMS):
        ref = pr.numpy_reference(cur[s].snapshot(block_size=64),
                                 iterations=300)
        sess = svc.sessions[s]
        errs.append(float(pr.linf(sess.ranks[:sess.n],
                                  jnp.asarray(ref[:sess.n]))))
    out["plan"] = {"seed": plan.seed, "steps": plan.steps,
                   "streams": plan.streams, "counts": plan.counts()}
    out["corruption_injected"] = injected
    out["corruption_detected"] = detected
    out["repaired_clean"] = repaired_clean
    out["repairs_by_rung"] = repairs_by_rung
    out["detection_latency_max_s"] = (round(max(detect_lat), 6)
                                      if detect_lat else 0.0)
    out["final_scrub_ok"] = all(r.ok for r in final.values())
    out["linf_vs_reference_max"] = max(errs)
    return out


_SHARDED_SCRIPT = textwrap.dedent("""
    import json
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro.api import EngineConfig, PageRankSession
    from repro.core import pagerank as pr
    from repro.core.delta import random_batch
    from repro.graphs.generators import rmat

    N_DEV, N_BATCHES, LOG2_N = %(n_dev)d, %(n_batches)d, %(log2_n)d
    hg0 = rmat(LOG2_N, avg_degree=6, seed=3)
    r0 = jnp.asarray(pr.numpy_reference(hg0.snapshot(block_size=64),
                                        iterations=300))
    batches = []
    cur = hg0
    for i in range(N_BATCHES):
        dels, ins = random_batch(cur, 2e-3, seed=700 + i)
        batches.append((dels, ins))
        cur = cur.apply_batch(dels, ins)
    ref = pr.numpy_reference(cur.snapshot(block_size=64), iterations=300)

    out = {"n_devices": N_DEV, "n": hg0.n, "batches": N_BATCHES,
           "partitioners": {}}
    for part in ("contiguous", "hash", "bfs_blocks"):
        sess = PageRankSession.from_graph(
            hg0, config=EngineConfig(topology="sharded", n_shards=N_DEV,
                                     partitioner=part), r0=r0)
        sess.warmup()
        for dels, ins in batches:
            assert sess.update(dels, ins).stats.converged
        rep = sess.report()
        out["partitioners"][part] = {
            "edge_cut": round(rep.edge_cut, 4),
            "p50_ms": round(rep.p50_s * 1e3, 3),
            "p95_ms": round(rep.p95_s * 1e3, 3),
            "retraces_post_warmup": rep.retraces_post_warmup,
            "total_sweeps": rep.total_sweeps,
            "collective_bytes_per_sweep": rep.collective_bytes_per_sweep,
            "linf_vs_reference": float(np.max(np.abs(
                sess.ranks[:sess.n] - ref[:sess.n]))),
        }
        sess.close()
    print("SHARDED-JSON:" + json.dumps(out))
""")


def _smoke_sharded() -> dict:
    """Sharded-topology scenario: the same DF stream through a
    ``topology="sharded"`` session on an 8-host-device mesh, once per
    partitioner.  Runs in a subprocess (the XLA device count is locked at
    first jax init — the benchmark process must keep its single device)
    and records per-partitioner edge-cut, p50/p95 update latency,
    post-warmup retraces (must be 0) and oracle parity.  The child is
    pinned to the CPU: forced host devices exist only there, and on an
    accelerator host the parent already holds the chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" \
        % SHARDED_DEVICES
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    script = _SHARDED_SCRIPT % {"n_dev": SHARDED_DEVICES,
                                "n_batches": SHARDED_BATCHES,
                                "log2_n": SHARDED_LOG2_N}
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError("sharded smoke subprocess failed:\n"
                           + out.stderr[-3000:])
    payload = [ln for ln in out.stdout.splitlines()
               if ln.startswith("SHARDED-JSON:")]
    return {"platform": "cpu",
            **json.loads(payload[-1][len("SHARDED-JSON:"):])}


_RECOVERY_CHILD = textwrap.dedent("""
    import sys, time
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from repro.api import EngineConfig, PageRankSession
    from repro.core import pagerank as pr
    from repro.core.delta import random_batch
    from repro.graphs.generators import kmer_chains

    store_dir, log2_n, kill_after = (sys.argv[1], int(sys.argv[2]),
                                     int(sys.argv[3]))
    hg = kmer_chains(1 << log2_n, seed=4)
    r0 = jnp.asarray(pr.numpy_reference(hg.snapshot(block_size=64),
                                        iterations=300))
    cfg = EngineConfig(engine="pallas", block_size=64, durability="wal",
                       checkpoint_interval=100)
    sess = PageRankSession.from_graph(hg, config=cfg, r0=r0,
                                      store_dir=store_dir)
    cur = hg
    for i in range(kill_after):
        dels, ins = random_batch(cur, 8 / cur.m, seed=60 + i)
        sess.update(dels, ins)
        cur = cur.apply_batch(dels, ins)
    print("RECOVERY-READY", flush=True)   # the parent SIGKILLs us here
    time.sleep(300)
""")


def _smoke_recovery() -> dict:
    """Process-fault scenario (docs/FAULTS.md): a subprocess runs a
    durable streaming session, is SIGKILLed mid-run, and the session is
    restored here — recovery wall time, replayed-batch count, post-restore
    retraces and parity against an uninterrupted session are recorded.
    Restore must be bit-for-bit (same r0, same batch seeds, same jitted
    hot path) with zero post-restore retraces.  The child is pinned to the
    CPU (the parent holds the accelerator, if any); restore and the oracle
    both run here, so parity compares like with like."""
    import select
    import shutil
    import signal
    import tempfile
    import numpy as np
    import jax.numpy as jnp
    from repro.api import EngineConfig, PageRankSession
    from repro.core import pagerank as pr
    from repro.core.delta import random_batch
    from repro.graphs.generators import kmer_chains

    hg = kmer_chains(1 << RECOVERY_LOG2_N, seed=4)
    r0 = jnp.asarray(pr.numpy_reference(hg.snapshot(block_size=64),
                                        iterations=300))
    n_total = RECOVERY_KILL_AFTER + RECOVERY_AFTER
    batches, cur = [], hg
    for i in range(n_total):
        dels, ins = random_batch(cur, 8 / cur.m, seed=60 + i)
        batches.append((dels, ins))
        cur = cur.apply_batch(dels, ins)

    oracle = PageRankSession.from_graph(
        hg, config=EngineConfig(engine="pallas", block_size=64), r0=r0)
    for dels, ins in batches:
        assert oracle.update(dels, ins).stats.converged

    store_dir = tempfile.mkdtemp(prefix="repro-recovery-")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    # child stderr goes to a FILE, not a pipe: a chatty XLA child filling
    # an undrained stderr pipe would deadlock against our stdout readline
    with tempfile.TemporaryFile(mode="w+") as err:
        child = subprocess.Popen(
            [sys.executable, "-c", _RECOVERY_CHILD, store_dir,
             str(RECOVERY_LOG2_N), str(RECOVERY_KILL_AFTER)],
            env=env, stdout=subprocess.PIPE, stderr=err, text=True)
        try:
            deadline = time.time() + 600
            line = ""
            while "RECOVERY-READY" not in line:
                if time.time() > deadline or (line == ""
                                              and child.poll() is not None):
                    err.seek(0)
                    raise RuntimeError("recovery child failed:\n"
                                       + err.read()[-3000:])
                # select-gate the readline so a silently hung child trips
                # the deadline instead of blocking forever
                ready, _, _ = select.select([child.stdout], [], [], 5.0)
                line = child.stdout.readline() if ready else ""
            os.kill(child.pid, signal.SIGKILL)   # crash-stop, no cleanup
            child.wait(timeout=60)
        finally:
            if child.poll() is None:
                child.kill()

    t0 = time.time()
    sess = PageRankSession.restore(store_dir)
    recovery_wall_s = time.time() - t0
    rep = sess.report()
    post = []
    for dels, ins in batches[RECOVERY_KILL_AFTER:]:
        post.append(sess.update(dels, ins))
    rep2 = sess.report()
    linf = float(np.max(np.abs(np.asarray(sess.R)
                               - np.asarray(oracle.R))))
    shutil.rmtree(store_dir, ignore_errors=True)
    return {
        "n": sess.n,
        "child_platform": "cpu",
        "killed_after_batches": RECOVERY_KILL_AFTER,
        "replayed_batches": rep.replayed_batches,
        "recovery_wall_s": round(recovery_wall_s, 4),
        "post_restore_batches": len(post),
        "post_restore_retraces": rep2.retraces_post_warmup,
        "post_restore_p50_ms": round(float(np.percentile(
            [r.wall_time_s for r in post], 50)) * 1e3, 3),
        "linf_vs_uninterrupted": linf,
    }


def _smoke_stream() -> dict:
    """Streaming scenario: K fixed-size delta batches through the
    recompile-free runtime (core/stream.py) at two graph sizes, once per
    driver (the fused pull driver and the residual forward-push driver on
    the same tile pool — docs/ENGINES.md).  Records per-batch p50/p95
    latency, the post-warmup retrace count of each fused driver (must be
    0), per-driver ``edges_processed`` totals with the pull/push ratio
    (the push acceptance signal: ≥5× fewer edges at equal L∞), and the
    large/small latency ratio — per-batch cost tracking batch size, not
    graph size, is the streaming acceptance signal."""
    import jax.numpy as jnp
    from repro.core import pagerank as pr
    from repro.core.delta import random_batch
    from repro.core.stream import run_stream
    from repro.graphs.generators import kmer_chains

    out = {"batch_edges": STREAM_BATCH_EDGES, "n_batches": STREAM_BATCHES,
           "sizes": {}}
    p50s = []
    for lg in STREAM_SIZES:
        hg = kmer_chains(1 << lg, seed=4)
        g = hg.snapshot(block_size=64)
        r0 = jnp.asarray(pr.numpy_reference(g, iterations=300))

        # materialize the batch list once (and its final graph, for the
        # parity oracle) — a single generation pass
        batch_list = []
        cur = hg
        for i in range(STREAM_BATCHES):
            dels, ins = random_batch(cur, STREAM_BATCH_EDGES / cur.m,
                                     seed=70 + i)
            batch_list.append((dels, ins))
            cur = cur.apply_batch(dels, ins)
        ref = pr.numpy_reference(cur.snapshot(block_size=64), iterations=300)

        reps = {}
        for driver in ("pull", "push"):
            reps[driver] = run_stream(hg, batch_list, block_size=64, r0=r0,
                                      active_policy="rc", driver=driver)
        rep, prep = reps["pull"], reps["push"]
        p50s.append(rep.p50_s)

        def _row(r):
            return {
                "p50_ms": round(r.p50_s * 1e3, 3),
                "p95_ms": round(r.p95_s * 1e3, 3),
                "retraces_post_warmup": r.retraces_post_warmup,
                "sweeps_last": r.results[-1].stats.sweeps,
                "edges_processed": int(sum(
                    b.stats.edges_processed for b in r.results)),
                "linf_vs_reference": float(pr.linf(
                    r.final_ranks[:g.n], jnp.asarray(ref[:g.n]))),
            }

        # the per-size row keeps the historical pull-driver schema at top
        # level (dashboards key on it) and nests the push row next to it
        row = {"n": g.n, "m": g.m, **_row(rep), "push": _row(prep)}
        row["edges_ratio_pull_over_push"] = round(
            row["edges_processed"] / max(row["push"]["edges_processed"], 1),
            3)
        row["p50_delta_ms_push_minus_pull"] = round(
            (prep.p50_s - rep.p50_s) * 1e3, 3)
        out["sizes"][str(1 << lg)] = row
    out["latency_ratio_large_over_small"] = round(p50s[-1] / p50s[0], 3)
    return out


def _smoke_ppr() -> dict:
    """Walk-engine personalized-PageRank scenario (the sweep-free engine's
    acceptance record).  Three measurements on one seeded power-law graph:

    * **accuracy vs R** — mean L1 error of the walk PPR estimate against
      the exact dense personalized oracle (``pr.ppr_numpy_reference``)
      over ``PPR_SEED_SETS`` seed sets, one point per R in
      ``PPR_R_CURVE`` (must shrink as R grows; gated at the largest R);
    * **per-delta localization** — regenerated-walk counts per update
      batch on a walk session (regenerated ≤ touched-walk mass < total
      walks, and 0 post-warmup retraces on the walk-buffer ladder);
    * **per-user serving** — ``PPR_USERS`` simulated users issuing
      seed-set top-k reads through a ``PageRankService`` (degraded-mode
      snapshot reads), recorded as query p50/p95.
    """
    import numpy as np
    from repro.api import EngineConfig, PageRankService, PageRankSession
    from repro.core import pagerank as pr
    from repro.core.delta import random_batch
    from repro.core.walk_engine import WalkState
    from repro.graphs.generators import powerlaw

    hg = powerlaw(PPR_N, PPR_AVG_DEG, seed=17)
    g = hg.snapshot(block_size=64)
    rng = np.random.default_rng(23)
    seed_sets = [rng.choice(PPR_N, PPR_SEEDS_PER_SET, replace=False)
                 for _ in range(PPR_SEED_SETS)]
    oracles = {tuple(s.tolist()): pr.ppr_numpy_reference(
        g, s, iterations=300) for s in seed_sets}

    out = {"graph": {"n": hg.n, "m": hg.m}, "walk_length": PPR_L,
           "seed_sets": PPR_SEED_SETS, "seeds_per_set": PPR_SEEDS_PER_SET,
           "l1_vs_R": {}}
    for R in PPR_R_CURVE:
        ws = WalkState(hg, R=R, L=PPR_L, seed=5)
        errs = []
        for s in seed_sets:
            est = np.asarray(ws.ppr(s))
            ref = oracles[tuple(s.tolist())][:hg.n]
            errs.append(float(np.abs(est - ref).sum()))
        out["l1_vs_R"][str(R)] = round(float(np.mean(errs)), 4)

    # -- per-delta localization on a live walk session -----------------------
    mid_r = PPR_R_CURVE[len(PPR_R_CURVE) // 2]
    cfg = EngineConfig(engine="walk", walks_per_vertex=mid_r,
                       walk_length=PPR_L, walk_seed=5)
    sess = PageRankSession.from_graph(hg, config=cfg)
    sess.warmup()
    cur = hg
    batches = []
    for j in range(PPR_BATCHES):
        dels, ins = random_batch(cur, PPR_BATCH_EDGES / cur.m, seed=900 + j)
        res = sess.update(dels, ins)
        cur = cur.apply_batch(dels, ins)
        batches.append({"regenerated_walks": res.regenerated_walks,
                        "touched_walks": res.touched_walks,
                        "total_walks": res.total_walks,
                        "wall_ms": round(res.wall_time_s * 1e3, 3)})
    rep = sess.report()
    out["localization"] = {
        "R": mid_r, "batches": batches,
        "retraces_post_warmup": rep.retraces_post_warmup,
        "bucket_retraces_post_warmup": rep.bucket_retraces_post_warmup,
    }
    sess.close()

    # -- 1k simulated users through the serving surface ----------------------
    svc = PageRankService([hg, hg], config=cfg)
    walls = []
    urng = np.random.default_rng(41)
    # one warm call per stream: the top-k query kernel legitimately
    # compiles once per (|S|, k) shape — users all share that shape
    for s in range(2):
        svc.ppr_query(s, urng.choice(PPR_N, PPR_SEEDS_PER_SET,
                                     replace=False), PPR_TOP_K)
    for u in range(PPR_USERS):
        seeds = urng.choice(PPR_N, PPR_SEEDS_PER_SET, replace=False)
        t0 = time.perf_counter()
        r = svc.ppr_query(u % 2, seeds, PPR_TOP_K)
        walls.append(time.perf_counter() - t0)
        assert len(r.values) == PPR_TOP_K
    out["serving"] = {
        "users": PPR_USERS, "top_k": PPR_TOP_K,
        "query_p50_ms": round(float(np.percentile(walls, 50)) * 1e3, 3),
        "query_p95_ms": round(float(np.percentile(walls, 95)) * 1e3, 3),
        "degraded_reads": True,
    }
    svc.stop()
    return out


def smoke(out: str = SMOKE_OUT) -> dict:
    """Tiny per-engine perf snapshot: one DF_LF dynamic update per engine,
    plus the streaming scenario (K delta batches, per-batch latency), the
    service scenario (N concurrent sessions with concurrent query clients,
    per-session p50/p95 + query staleness), the serve_load scenario
    (durable streams at 2x overload with shedding, degraded reads and a
    watchdog-recovered slot kill), the chaos scenario (a seeded
    composed-fault soak: silent corruption injected and repaired via the
    integrity subsystem, gated on detection and repair-ladder coverage)
    and the sharded scenario (a topology="sharded" session on an
    8-host-device mesh, per-partitioner edge-cut/latency).

    Records sweeps, edges_processed, wall time and the frontier-work ratio
    edges_processed / (m · sweeps) — the Pallas engine's ratio ≪ 1 is the
    "frontier-proportional work" acceptance signal; the stream section's
    flat per-batch latency with 0 post-warmup retraces is the streaming
    acceptance signal.  Wired into tier-1 as a non-failing step
    (tests/test_bench_smoke.py) so the perf trajectory is recorded on
    every run.
    """
    from benchmarks.common import updated_snapshots  # noqa: F401 (jax cfg)
    import jax.numpy as jnp
    from repro.core import pagerank as pr
    from repro.core import pallas_engine as pe
    from repro.core.delta import random_batch
    from repro.core.frontier import batch_to_device
    from repro.graphs.generators import kmer_chains
    from repro.kernels.block_spmv import ops

    # k-mer chains: the paper's locality-friendly class — a tiny batch's
    # perturbation stays inside the touched chains, so frontier work is
    # visibly ≪ |E| per sweep even at container scale (64 blocks)
    hg0 = kmer_chains(1 << 12, seed=4)
    g0 = hg0.snapshot(block_size=64)
    r_prev = jnp.asarray(pr.numpy_reference(g0, iterations=300))
    dels, ins = random_batch(hg0, 2e-4, seed=7)
    hg1 = hg0.apply_batch(dels, ins)
    g1 = hg1.snapshot(block_size=64)
    ref1 = pr.numpy_reference(g1, iterations=300)
    batch = batch_to_device(g1, dels, ins)

    report = {"graph": {"n": g1.n, "m": g1.m,
                        "batch_edges": int(len(dels) + len(ins))},
              "engines": {}}
    # dense runs BB (full SpMV per iteration: the work_ratio≈1 baseline);
    # the frontier engines run the paper's DF_LF with the per-chunk
    # converged-flag policy ("rc", §4.3).  The pallas pull matrix is built
    # once outside the timed calls (in production it is maintained
    # incrementally), so the warm second call is true steady state.  The
    # pallas engine runs its platform tile backend (ops.default_backend():
    # Pallas kernels on TPU, the XLA tile path on CPU containers).
    pmat = pe.build_pull_matrix(g1)
    for engine, mode in (("dense", "bb"), ("blocked", "lf"),
                         ("pallas", "lf")):
        ekw = {"pallas_mat": pmat} if engine == "pallas" else {}

        def go():
            return pr.df_pagerank(g0, g1, batch, r_prev, mode=mode,
                                  engine=engine, active_policy="rc", **ekw)
        res = go()
        res = go()      # second call = warm jit caches → steady-state time
        s = res.stats
        report["engines"][engine] = {
            "mode": mode,
            "converged": bool(res.converged),
            "sweeps": int(s.sweeps),
            "edges_processed": int(s.edges_processed),
            "frontier_work_ratio": (
                s.edges_processed / (g1.m * max(s.sweeps, 1))),
            "wall_time_s": round(res.wall_time_s, 4),
            "linf_vs_reference": float(pr.linf(res.ranks[:g1.n],
                                               ref1[:g1.n])),
        }
        if engine == "pallas":
            report["engines"][engine]["backend"] = ops.default_backend()

    report["stream"] = _smoke_stream()
    report["service"] = _smoke_service()
    report["serve_load"] = _smoke_serve_load()
    report["chaos"] = _smoke_chaos()
    report["sharded"] = _smoke_sharded()
    report["recovery"] = _smoke_recovery()
    report["ppr"] = _smoke_ppr()

    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(f"# smoke report written to {os.path.abspath(out)}")
    return report


SECTIONS = [
    ("Fig 1  (chunk/block-size trade-off)", "benchmarks.bench_chunk_tradeoff"),
    ("Fig 5  (temporal graphs)", "benchmarks.bench_temporal"),
    ("Fig 6  (strong scaling)", "benchmarks.bench_scaling"),
    ("Fig 7  (batch-size sweep + error)", "benchmarks.bench_batch_sweep"),
    ("S5.2.3 (stability)", "benchmarks.bench_stability"),
    ("Fig 8/9 (delays + crashes)", "benchmarks.bench_faults"),
    ("kernels (pallas block-SpMV)", "benchmarks.bench_kernels"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="per-engine smoke snapshot → BENCH_smoke.json")
    ap.add_argument("--only", default=None,
                    help="substring filter on section names")
    args = ap.parse_args()
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        smoke()
        return

    failures = []
    for title, module in SECTIONS:
        if args.only and args.only not in module and args.only not in title:
            continue
        print(f"\n===== {title} [{module}] =====", flush=True)
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main(quick=args.quick)
            print(f"# section done in {time.time() - t0:.1f}s")
        except Exception as e:
            failures.append((module, e))
            traceback.print_exc()
    print("\n===== roofline (from dry-run artifacts) =====", flush=True)
    try:
        from benchmarks import roofline
        roofline.main()
    except Exception as e:
        failures.append(("benchmarks.roofline", e))
        traceback.print_exc()

    if failures:
        print(f"\n{len(failures)} benchmark section(s) FAILED: "
              f"{[m for m, _ in failures]}")
        sys.exit(1)
    print("\nall benchmark sections completed")


if __name__ == "__main__":
    main()
