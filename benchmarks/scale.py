"""Tiered-storage scaling bench — host-paged cold tiles, device hot set.

Drives streaming DF sessions over a size ladder under *shrinking device
budgets* (``EngineConfig.device_budget_bytes``) and records, per
(n, budget) row:

  * p50 / p95 per-batch update latency,
  * device bytes by component (tile pool / slot tables / operand mirrors /
    walk buffers) and bytes/vertex, from ``report()``'s memory audit,
  * hot-set hit rate and the full tiering counter block,
  * checkpoint + restore wall time (durability is budget-independent:
    ``save()`` serializes host truth, so these should be flat across
    budgets at fixed n),
  * post-warmup retraces (must be 0 — the hot path stays compile-free
    under admission/eviction because gathers are bucket-padded).

Plus a blocked-oracle parity check at the largest dense-fitting size of
the tier (full snapshot + ``run_blocked`` vs the tiered session's ranks),
and an R-MAT/power-law row at modest n: dense 64x64 tiles make
low-locality power-law graphs pool-quadratic (every edge lands in its own
tile), so the *scaling curve* uses the road-network family the tiering is
built for while the R-MAT row records the adversarial datapoint.

Tiers::

    python -m benchmarks.scale --smoke    # CI tier: n = 4K..16K, seconds
    python -m benchmarks.scale            # default: n = 64K..262K
    python -m benchmarks.scale --full     # adds the n = 1M acceptance row

``--driver push`` runs the ladder under the residual forward-push driver
(same tile pool, work ∝ residual mass — docs/ENGINES.md); the smoke tier
always appends one push row at half budget so BENCH_scale.json records
the push-under-tiering datapoint on every CI run.

The multi-million extension beyond ``--full`` (n = 4M, side 2048) is a
manual run: same command with ``--side 2048`` after confirming ~20 GB of
host headroom for the tile pool — see docs/SCALE.md for the sizing rule.

Rows warm-start from a host-computed reference (``_reference_ranks``):
the bench measures *streaming* behavior under a budget, and the cold
solve is engine-bound and budget-independent (deployments restore from
checkpoints; the tiered cold-solve path is tested at small n in
tests/test_tiering.py).

Writes ``BENCH_scale.json`` at the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time
from typing import List, Optional

import numpy as np

from repro.api import EngineConfig, PageRankSession
from repro.core import blocked as blk
from repro.core import pagerank as pr
from repro.core import tiering
from repro.graphs.generators import grid_road, rmat

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_scale.json")

# (side, tau, batches, batch_edges) per tier; n = side^2
SMOKE_LADDER = ((64, 1e-8, 4, 16), (128, 1e-8, 4, 16))
DEFAULT_LADDER = ((256, 1e-7, 4, 32), (512, 1e-7, 3, 32))
FULL_LADDER = ((1024, 1e-6, 2, 32),)

BUDGET_FRACS = (1.0, 0.5)
SMOKE_EXTRA_FRAC = 0.25          # smallest smoke row also runs quarter-budget


def _pool_bytes(hg, block_size: int = 64) -> int:
    """Host-tier size of the full tile pool for this graph (the number the
    budget fractions are taken against)."""
    g0 = hg.snapshot(block_size=block_size)
    src, dst = g0.in_edges_host()
    pool = tiering.HostTilePool.from_edges(
        dst, src, g0.n_pad, g0.n_pad, block=block_size,
        dtype=np.dtype(np.float32))
    return int(pool.nbytes)


def _local_batch(rng, n: int, k: int, window: int = 4096) -> np.ndarray:
    """Insertion batch with temporal locality: endpoints drawn from one
    random window of ids (real streams touch a working set, not the whole
    id space — and a graph-wide batch makes every row-block hot, which
    benchmarks the engine, not the tiering)."""
    base = int(rng.integers(0, max(n - window, 1)))
    return base + rng.integers(0, min(window, n), (k, 2))


def _reference_ranks(hg) -> np.ndarray:
    """Host-computed warm start (f64 bincount power iteration).  The bench
    measures *streaming* behavior under a budget; the cold solve is
    engine-bound and identical across budgets, so every row starts from
    the same converged reference (real deployments restore from a
    checkpoint).  The tiered cold-solve path itself is covered at small n
    in tests/test_tiering.py."""
    g = hg.snapshot(block_size=64)
    return pr.numpy_reference(g, iterations=200).astype(np.float32)


def _run_row(hg, *, tau: float, batches: int, batch_edges: int,
             budget_frac: float, pool_bytes: int, seed: int,
             graph_name: str, r0: Optional[np.ndarray] = None,
             driver: str = "pull") -> dict:
    import jax.numpy as jnp
    n = hg.n
    budget = max(int(pool_bytes * budget_frac), 1)
    cfg = EngineConfig(engine="pallas", tau=tau, block_size=64,
                       dtype="float32", device_budget_bytes=budget,
                       driver=driver)
    t0 = time.perf_counter()
    sess = PageRankSession.from_graph(
        hg, config=cfg, r0=None if r0 is None else jnp.asarray(r0))
    init_s = time.perf_counter() - t0
    sess.warmup()

    rng = np.random.default_rng(seed)
    walls: List[float] = []
    converged = 0
    for _ in range(batches):
        ins = _local_batch(rng, n, batch_edges)
        dels = np.zeros((0, 2), np.int64)
        t0 = time.perf_counter()
        res = sess.update(dels, ins)
        walls.append(time.perf_counter() - t0)
        converged += int(res.stats.converged)

    rep = sess.report()
    # durability is budget-independent: save() walks host truth
    tmp = tempfile.mkdtemp(prefix="bench_scale_ckpt_")
    try:
        t0 = time.perf_counter()
        sess.save(tmp)
        ckpt_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        restored = PageRankSession.restore(tmp)
        restore_s = time.perf_counter() - t0
        restore_linf = float(np.max(np.abs(
            np.asarray(restored.ranks) - np.asarray(sess.ranks))))
        restored.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    row = {
        "graph": graph_name,
        "n": n,
        "m": hg.m,
        "driver": driver,
        "budget_frac": budget_frac,
        "budget_bytes": budget,
        "pool_bytes": pool_bytes,
        "tau": tau,
        "batches": batches,
        "batch_edges": batch_edges,
        "batches_converged": converged,
        "init_s": round(init_s, 4),
        "p50_batch_s": round(float(np.percentile(walls, 50)), 4),
        "p95_batch_s": round(float(np.percentile(walls, 95)), 4),
        "ckpt_s": round(ckpt_s, 4),
        "restore_s": round(restore_s, 4),
        "restore_linf": restore_linf,
        "retraces_post_warmup": rep.retraces_post_warmup,
        "bucket_retraces_post_warmup": rep.bucket_retraces_post_warmup,
        "hit_rate": rep.tiering["hit_rate"],
        "tiering": rep.tiering,
        "device_bytes": rep.device_bytes,
        "bytes_per_vertex": round(rep.bytes_per_vertex, 2),
    }
    final_ranks = np.asarray(sess.ranks).copy()
    final_hg = sess.hg
    sess.close()
    return row, final_ranks, final_hg


def _oracle_parity(hg, ranks: np.ndarray, *, tau: float) -> dict:
    """Blocked Gauss-Seidel oracle on the final snapshot vs the tiered
    session's served ranks (the dense-fitting cross-engine check).  The
    oracle warm-starts from its own host reference — it still converges
    to its own fixed point, just without paying 100+ cold sweeps."""
    import jax.numpy as jnp
    g = hg.snapshot(block_size=64)
    R0 = jnp.asarray(pr.numpy_reference(g, iterations=200)
                     .astype(np.float32))
    R, st = blk.run_blocked(g, R0, g.vertex_valid, mode="lf", tau=tau,
                            active_policy="rc")
    linf = float(np.max(np.abs(np.asarray(R)[:g.n] - ranks[:g.n])))
    return {"n": g.n, "m": g.m, "linf": linf,
            "oracle_converged": bool(st.converged)}


def main(*, smoke: bool = False, full: bool = False,
         side: Optional[int] = None, driver: str = "pull",
         out: str = OUT) -> dict:
    if smoke:
        ladder = SMOKE_LADDER
    elif full:
        ladder = DEFAULT_LADDER + FULL_LADDER
    else:
        ladder = DEFAULT_LADDER
    if side is not None:            # manual multi-million extension
        ladder = ladder + ((side, 1e-6, 2, 32),)

    import jax
    report = {
        "meta": {
            "tier": ("smoke" if smoke else "full" if full else "default"),
            "backend": jax.default_backend(),
            "warm_start": "host_reference",
            "budget_fracs": list(BUDGET_FRACS),
            "driver": driver,
            "generated_unix": int(time.time()),
        },
        "rows": [],
    }

    parity_candidate = None
    for i, (s, tau, batches, batch_edges) in enumerate(ladder):
        hg = grid_road(s, seed=7)
        pool_b = _pool_bytes(hg)
        r0 = _reference_ranks(hg)
        fracs = BUDGET_FRACS
        if smoke and i == 0:
            fracs = BUDGET_FRACS + (SMOKE_EXTRA_FRAC,)
        if s >= 1024:
            # the acceptance row needs budget < pool; a second full-budget
            # pass would double an engine-bound hour for no new signal
            fracs = (0.5,)
        for frac in fracs:
            row, ranks, final_hg = _run_row(
                hg, tau=tau, batches=batches, batch_edges=batch_edges,
                budget_frac=frac, pool_bytes=pool_b, seed=11 + i,
                graph_name=f"grid_road({s})", r0=r0, driver=driver)
            report["rows"].append(row)
            print(f"[scale] {row['graph']} {driver} budget={frac} "
                  f"p50={row['p50_batch_s']}s hit={row['hit_rate']:.3f} "
                  f"retr={row['retraces_post_warmup']}", flush=True)
            # parity at the LARGEST dense-fitting size: track the biggest
            # sub-budget row whose oracle run is affordable (n <= 262144)
            if frac < 1.0 and hg.n <= 262144:
                parity_candidate = (final_hg, ranks, tau)

    # the adversarial power-law datapoint (modest n: dense tiles make
    # R-MAT pool-quadratic — recorded, not scaled)
    rm = rmat(12, 8, seed=9, chunk_edges=1 << 15)
    pool_b = _pool_bytes(rm)
    row, ranks, final_hg = _run_row(
        rm, tau=1e-8, batches=3, batch_edges=16, budget_frac=0.5,
        pool_bytes=pool_b, seed=3, graph_name="rmat(2^12)",
        r0=_reference_ranks(rm), driver=driver)
    report["rows"].append(row)
    if parity_candidate is None:
        parity_candidate = (final_hg, ranks, 1e-8)

    # the push-driver datapoint under a budget (driver="push" composes
    # with tiering: a push to a non-resident row defers into the refill
    # bitmap — docs/ENGINES.md).  Recorded, not a parity candidate; the
    # full push ladder is `--driver push`.
    if smoke and driver == "pull":
        s0 = SMOKE_LADDER[0][0]
        hg_push = grid_road(s0, seed=7)
        row, _, _ = _run_row(
            hg_push, tau=SMOKE_LADDER[0][1], batches=SMOKE_LADDER[0][2],
            batch_edges=SMOKE_LADDER[0][3], budget_frac=0.5,
            pool_bytes=_pool_bytes(hg_push), seed=11,
            graph_name=f"grid_road({s0})", r0=_reference_ranks(hg_push),
            driver="push")
        report["rows"].append(row)
        print(f"[scale] {row['graph']} push budget=0.5 "
              f"p50={row['p50_batch_s']}s retr="
              f"{row['retraces_post_warmup']}", flush=True)

    hg_p, ranks_p, tau_p = parity_candidate
    report["oracle_parity"] = _oracle_parity(hg_p, ranks_p, tau=tau_p)
    print(f"[scale] oracle parity n={report['oracle_parity']['n']} "
          f"linf={report['oracle_parity']['linf']:.3e}", flush=True)

    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI tier: tiny ladder, seconds")
    ap.add_argument("--full", action="store_true",
                    help="adds the n=1M acceptance row")
    ap.add_argument("--side", type=int, default=None,
                    help="manual extension: extra grid side (n = side^2)")
    ap.add_argument("--driver", choices=("pull", "push"), default="pull",
                    help="convergence driver for the ladder rows "
                         "(docs/ENGINES.md; smoke tier always appends one "
                         "push datapoint)")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()
    from benchmarks.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(smoke=args.smoke, full=args.full, side=args.side,
         driver=args.driver, out=args.out)
