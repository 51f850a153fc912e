"""Quickstart — the paper's workload end-to-end, on the session API.

Opens one :class:`repro.api.PageRankSession` over a dynamic road-network
graph and maintains PageRank through a stream of batch updates with the
lock-free Dynamic Frontier engine (DF_LF): each ``update`` is the
recompile-free O(batch) hot path.  Every update is validated against the
reference solver and compared with the Naive-dynamic baseline (ND_LF) run
on a throwaway ``fork()`` of the same session — the what-if mechanism.

    PYTHONPATH=src python examples/quickstart.py [--batches 5]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax

from repro.api import EngineConfig, PageRankSession              # noqa: E402
from repro.core import pagerank as pr                            # noqa: E402
from repro.core.delta import random_batch                        # noqa: E402
from repro.graphs.generators import grid_road                    # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=5)
    ap.add_argument("--batch-frac", type=float, default=1e-5)
    ap.add_argument("--side", type=int, default=256)
    args = ap.parse_args()

    print("building dynamic graph (road-network class)...")
    hg = grid_road(args.side, seed=0)
    print(f"  |V|={hg.n:,}  |E|={hg.m:,}")

    # ranks in the platform's default dtype: f32 unless the caller enables
    # x64 (the paper's f64; a TPU has none).  In f32, tau is 1e-4 of the
    # mean rank and the error band follows from it (see chip_smoke.py)
    f64 = jax.config.jax_enable_x64
    tau = 1e-10 if f64 else 1e-4 / hg.n
    band = 1e-9 if f64 else 2 * tau / (1 - 0.85)

    # one handle owns graph state, ranks and the incremental engine
    # operands; construction runs the initial solve
    sess = PageRankSession.from_graph(
        hg, config=EngineConfig(engine="pallas", tau=tau, block_size=64))
    sess.warmup()     # trace the per-batch pipeline → steady-state timings
    print("initial PageRank computed; streaming batch updates:\n")

    tot_df, tot_nd = 0.0, 0.0
    for step in range(args.batches):
        dels, ins = random_batch(sess.hg, args.batch_frac, seed=100 + step)
        nd_sess = sess.fork()           # what-if branch: same state, no copy

        df = sess.update(dels, ins)                       # DF_LF hot path
        nd = nd_sess.update(dels, ins, variant="nd")      # ND_LF baseline

        ref = pr.reference_pagerank(sess.hg.snapshot(block_size=64),
                                    iterations=250)
        err = pr.linf(df.ranks, ref[:df.ranks.shape[0]])
        tol = band + 64 * 1.2e-7 * float(ref.max())
        assert err < tol, f"error {err} out of the {tol:.1e} band"
        if step > 0:    # step 0 pays the ND path's (expand=False) jit trace
            tot_df += df.wall_time_s
            tot_nd += nd.wall_time_s
        print(f"batch {step}: |Δ|={len(dels) + len(ins):4d}  "
              f"DF_LF {df.wall_time_s:6.3f}s ({df.stats.sweeps} sweeps, "
              f"{df.stats.edges_processed / 1e6:6.2f}M edges)   "
              f"ND_LF {nd.wall_time_s:6.3f}s ({nd.stats.sweeps} sweeps, "
              f"{nd.stats.edges_processed / 1e6:6.2f}M edges)   "
              f"L_inf={err:.2e}")

    rep = sess.report()
    vals, ids = sess.top_k(5)           # device-side: 5 values transferred
    print(f"\nsession report: {rep.n_updates} updates, "
          f"p50 {rep.p50_s * 1e3:.1f} ms, p95 {rep.p95_s * 1e3:.1f} ms, "
          f"retraces post-warmup: {rep.retraces_post_warmup}")
    print("top-5 vertices: "
          + ", ".join(f"{i}={v:.2e}" for i, v in zip(ids, vals)))
    if tot_df > 0:
        print(f"DF_LF vs ND_LF wall-time speedup "
              f"(excl. warm-up): {tot_nd / tot_df:.2f}x")
    print(f"all updates stayed within the {band:.1e} error band ✓")


if __name__ == "__main__":
    main()
