"""Smoke run of the DF_LF update stream on one TPU chip, at n = 1,048,576.

Drives the paper's main path through the entry points a user calls: a
``PageRankSession`` in stream mode on the pallas engine (compiled Pallas
tile kernels, fused pull driver), fed seeded edge batches and read back
with ``query`` / ``top_k``.  The same graph and batches then run through
the push driver, through tiered storage at half the allocated tile pool
(``benchmarks/scale.py``'s budget fraction 0.5), and through
a two-session ``PageRankService``.  Every phase is checked against the
numpy reference of the final graph (``pagerank.numpy_reference``).

    python chip_smoke.py              # one chip: pull, push, tiered, service
    python chip_smoke.py --chips 4    # only: a 4-shard session vs one chip

Runs in one process and starts none.  It exits non-zero, printing no
result line, when JAX finds no TPU or any check fails.  The last line of
standard output is one JSON object naming the device.  Compiled programs
are kept in ``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
sys.path.insert(0, HERE)

import numpy as np                                            # noqa: E402

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from benchmarks.compile_cache import enable_compile_cache     # noqa: E402
from repro.api import (EngineConfig, PageRankService,         # noqa: E402
                       PageRankSession, ServingConfig)
from repro.core import pagerank as pr                         # noqa: E402
from repro.core.delta import random_batch                     # noqa: E402
from repro.graphs.generators import grid_road                 # noqa: E402

SIDE = 1024                 # grid_road(1024): n = 1,048,576 (scale.py FULL)
BLOCK = 64
BATCHES = 8
BATCH_EDGES = 32
ALPHA = 0.85
# f32 ranks, tau = TAU_REL / n: 1e-4 of the mean rank.  That is the
# tau ~ 1e-7 docs/ENGINES.md gives for f32 at n ~ 1K, and 9.54e-11 at
# n = 1,048,576, where ranks are ~1e-6 and their f32 ulp ~1e-13: three
# decades above the rounding floor, below which sweeps would jitter.
TAU_REL = 1e-4
F32_EPS = float(np.finfo(np.float32).eps)
EXPECT_INTERPRET = False
PLATFORM = "tpu"


def linf_tol(ref: np.ndarray, tau: float) -> float:
    """A vertex stops once an update moves it by <= tau, so each batch
    leaves a tau-approximate fixed point: an error of tau / (1 - ALPHA)
    per unit gain of the iteration, taken twice, plus f32 rounding of the
    largest rank."""
    return 2.0 * tau / (1.0 - ALPHA) + 64.0 * F32_EPS * float(ref.max())


def log(msg: str) -> None:
    print(msg, flush=True)


class Timer:
    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"[time] {self.name}: {self.s:.3f} s")
        return False


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"[ok] {what}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# workload: one seeded graph, its batches, references before and after
# ---------------------------------------------------------------------------

def make_workload(side: int, seed: int) -> dict:
    with Timer("host build of graph, batches and references"):
        hg = grid_road(side, seed=seed)
        batches, cur = [], hg
        for i in range(BATCHES):
            dels, ins = random_batch(cur, BATCH_EDGES / cur.m,
                                     seed=seed * 1000 + i)
            batches.append((dels, ins))
            cur = cur.apply_batch(dels, ins)
        r0 = pr.numpy_reference(hg.snapshot(block_size=BLOCK),
                                alpha=ALPHA, iterations=200)
        ref = pr.numpy_reference(cur.snapshot(block_size=BLOCK),
                                 alpha=ALPHA, iterations=200)[:hg.n]
    tau = TAU_REL / hg.n
    log(f"graph grid_road({side}) seed={seed}: n={hg.n} m={hg.m}; "
        f"{BATCHES} batches of {BATCH_EDGES} edges; final m={cur.m}; "
        f"tau={tau:.3e} tol={linf_tol(ref, tau):.3e}")
    return {"hg": hg, "batches": batches,
            "r0": r0[:hg.n].astype(np.float32), "ref": ref, "tau": tau,
            "tol": linf_tol(ref, tau)}


def config(w: dict, **kw) -> EngineConfig:
    return EngineConfig(engine="pallas", backend="pallas", tau=w["tau"],
                        alpha=ALPHA, block_size=BLOCK, dtype="float32", **kw)


def open_session(w: dict, cfg: EngineConfig) -> PageRankSession:
    with Timer(f"session build ({cfg.driver}, topology={cfg.topology}, "
               f"budget={cfg.device_budget_bytes})"):
        sess = PageRankSession.from_graph(w["hg"], config=cfg,
                                          r0=jnp.asarray(w["r0"]))
    if cfg.topology == "single":
        check(sess.engine_name == "pallas" and sess.backend == "pallas"
              and sess.interpret is EXPECT_INTERPRET
              and sess.R.dtype == jnp.float32,
              f"engine={sess.engine_name} backend={sess.backend} "
              f"interpret={sess.interpret} dtype={sess.R.dtype}")
    with Timer("warmup (compile)"):
        sess.warmup()
    return sess


def drive(sess: PageRankSession, w: dict, label: str) -> np.ndarray:
    for i, (dels, ins) in enumerate(w["batches"]):
        res = sess.update(dels, ins)
        log(f"[{label}] batch {i}: {res.wall_time_s:.4f} s, "
            f"sweeps={res.stats.sweeps} edges={res.stats.edges_processed} "
            f"converged={res.converged}")
    rep = sess.report()
    check(rep.batches_converged == BATCHES and rep.sweep_cap_hits == 0,
          f"[{label}] {rep.batches_converged}/{BATCHES} batches converged")
    check(rep.retraces_post_warmup == 0,
          f"[{label}] retraces_post_warmup={rep.retraces_post_warmup}")
    ref, tol = w["ref"], w["tol"]
    ranks = np.asarray(sess.ranks)[:len(ref)]
    linf = float(np.max(np.abs(ranks - ref)))
    check(linf <= tol, f"[{label}] L_inf vs numpy reference {linf:.3e} "
                       f"<= {tol:.3e}")
    probe = np.random.default_rng(5).integers(0, len(ref), 16)
    q = sess.query(probe)
    check(float(np.max(np.abs(q - ref[probe]))) <= tol,
          f"[{label}] query of 16 vertices matches the reference")
    vals, ids = sess.top_k(10)
    check(float(np.max(np.abs(vals - np.sort(ref)[::-1][:10]))) <= tol
          and float(np.max(np.abs(ref[ids] - vals))) <= tol,
          f"[{label}] top_k(10) matches the reference")
    log(f"[{label}] peak_bytes_in_use={peak_bytes()}")
    return ranks


def release(*objs) -> None:
    for o in objs:
        if isinstance(o, (PageRankSession, PageRankService)):
            o.close()
    gc.collect()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_pull(w: dict) -> int:
    sess = open_session(w, config(w))
    mat = sess.inc.mat
    live = mat.n_tiles() * mat.block * mat.block * 4
    rep = sess.report()
    log(f"[pull] tile pool: {live} B live ({mat.n_tiles()} tiles), "
        f"{rep.device_bytes['tile_pool']} B allocated "
        f"(capacity {mat.tile_capacity}, max_tiles {mat.max_tiles}); "
        f"{rep.bytes_per_vertex:.1f} B/vertex on device")
    drive(sess, w, "pull")
    release(sess)
    return rep.device_bytes["tile_pool"]


def phase_push(w: dict) -> None:
    sess = open_session(w, config(w, driver="push"))
    drive(sess, w, "push")
    release(sess)


def phase_tiered(w: dict, pool_bytes: int) -> None:
    sess = open_session(w, config(w, device_budget_bytes=pool_bytes // 2))
    drive(sess, w, "tiered")
    log(f"[tiered] counters {json.dumps(sess.report().tiering)}")
    release(sess)


def phase_service(w: dict) -> None:
    sessions = [open_session(w, config(w)) for _ in range(2)]
    svc = PageRankService(sessions, serving=ServingConfig(), warmup=False)
    submitted = 0
    for dels, ins in w["batches"][:4]:
        for s in range(2):
            svc.submit(s, dels, ins)
            submitted += 1
    with Timer("service drain"):
        done = svc.run_until_drained()
    rep = svc.report()
    log(f"[service] requests_done={rep['requests_done']} "
        f"retries={rep['retries']} failovers={len(rep['failovers'])} "
        f"request_p50_ms={rep['request_p50_ms']}")
    errors = [r for r in done if r.error is not None or not r.done]
    check(len(done) == submitted and not errors,
          f"[service] {len(done)}/{submitted} requests done, "
          f"{len(errors)} errors")
    check(rep["retries"] == 0 and not rep["failovers"]
          and not any(s is None or s.closed for s in svc.sessions),
          "[service] 0 retries, 0 dead slots")
    # the service ran the first half of the batches: its reference is that
    # graph's, rebuilt here
    cur = w["hg"]
    for dels, ins in w["batches"][:4]:
        cur = cur.apply_batch(dels, ins)
    ref = pr.numpy_reference(cur.snapshot(block_size=BLOCK), alpha=ALPHA,
                             iterations=200)[:cur.n]
    tol = linf_tol(ref, w["tau"])
    probe = np.random.default_rng(6).integers(0, cur.n, 16)
    for s in range(2):
        q = np.asarray(svc.query(s, probe))
        vals, ids = svc.top_k(s, 10)
        check(float(np.max(np.abs(q - ref[probe]))) <= tol
              and float(np.max(np.abs(ref[ids] - vals))) <= tol,
              f"[service] stream {s} query/top_k match the reference")
    log(f"[service] peak_bytes_in_use={peak_bytes()}")
    release(*svc.sessions)


def phase_sharded(w: dict, n_chips: int) -> None:
    sess = open_session(w, EngineConfig(
        topology="sharded", n_shards=n_chips, tau=w["tau"], alpha=ALPHA,
        block_size=BLOCK, dtype="float32"))
    def check_placement(when: str) -> None:
        devs = sess.R.devices()
        check(len(devs) == n_chips
              and all(d.platform == PLATFORM for d in devs),
              f"[sharded] {when}: ranks live on {len(devs)} distinct "
              f"{PLATFORM} devices {sorted(d.id for d in devs)}")

    check_placement("after warmup")
    sharded = drive(sess, w, "sharded")
    check_placement("after the batches")
    release(sess)
    one = open_session(w, config(w))
    single = drive(one, w, "one-chip")
    release(one)
    diff = float(np.max(np.abs(sharded - single)))
    check(diff <= w["tol"], f"[sharded] L_inf vs the one-chip session "
                            f"{diff:.3e} <= {w['tol']:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-shard session and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()

    for var in ("REPRO_ENGINE", "REPRO_TILE_BACKEND"):
        if os.environ.get(var, "pallas") != "pallas":
            log(f"{var}={os.environ[var]!r} would divert the chip path "
                "from pallas/pallas")
            return 2
    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        log(f"no TPU: JAX found platform {dev.platform!r}")
        return 3
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, found {n_dev}")
        return 3
    log(f"device_kind={dev.device_kind} devices={n_dev} "
        f"jax={jax.__version__} x64={jax.config.jax_enable_x64}")
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    w = make_workload(SIDE, args.seed)
    if args.chips == 4:
        phase_sharded(w, 4)
    else:
        pool_bytes = phase_pull(w)
        phase_push(w)
        phase_tiered(w, pool_bytes)
        phase_service(w)
    log(f"[time] total: {time.perf_counter() - t0:.1f} s; "
        f"peak_bytes_in_use={peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
