"""Fused Pallas frontier engine — the whole DF_LF sweep loop on-device.

The blocked engine (:mod:`repro.core.blocked`) drives its sweeps from Python:
every iteration pays a host↔device round-trip for the active count, the
convergence flag and the per-sweep stats, and the pull itself is a
``segment_sum`` gather with no MXU mapping.  This engine removes both costs:

  1. the pull runs through the block-sparse tile SpMV
     (:mod:`repro.kernels.block_spmv`) over *compacted active row-block
     ids* — a sweep touches only frontier blocks and each touched block is
     a dense B×B tile (sum semiring).  Two backends share the layout: the
     Pallas kernels (MXU on TPU, scalar-prefetched ids) and an XLA
     gather/einsum path that makes CPU containers fast too
     (``ops.default_backend`` picks per platform);
  2. Dynamic Frontier expansion is the same kernel in the OR semiring,
     restricted to the *candidate* row-blocks whose tiles intersect a
     changed column-block (tile-presence adjacency, maintained
     incrementally across a stream);
  3. the driver is a single ``lax.while_loop`` containing compaction,
     the sweep, the τ/RC convergence test and fault-mask application.
     Zero host syncs until convergence; stats come back as one device
     array.  Kernel launches are *frontier-proportional*: the active-count
     selects a bucket from a static doubling ladder via ``lax.switch``
     (``ops.block_spmv_active_bucketed``), so the grid scales with the
     actual frontier instead of ``n_rb``.

The driver deliberately does **not** consume a :class:`GraphSnapshot`: its
operands are the per-vertex vectors (``valid``, ``out_deg``), the per-block
degree vectors (``rb_in``/``rb_out``), the tile-presence adjacency ``bmat``
and the capacity-padded pull matrix.  All of those keep stable shapes (and
stable pytree aux) across a dynamic stream, so after one warmup trace a
stream of delta batches re-enters the compiled driver with **zero
retraces** — a snapshot's ``m`` changing per batch would otherwise retrace
on nearly every step (see :mod:`repro.core.stream`).

Within a sweep the update is block-Jacobi (all active blocks read the
sweep-start ranks) — the lock-free *scheduling* semantics of DF_LF (per-block
work pool, per-vertex RC termination, τ_f-gated expansion, crash/delay
masks) are preserved — as in the blocked engine, a delayed or crashed
thread's slots are picked up by the surviving threads (charged to simulated
time), never deferred — while the blocked engine's in-sweep Gauss–Seidel
ordering is traded for barrier-free device execution.  Both converge to the
same fixed point within the paper's τ_f error bound; the blocked engine
remains as the Gauss–Seidel oracle.

On CPU containers the Pallas kernels would run in interpret mode
(``interpret=True``, semantics-validating only) — production CPU runs use
``backend="xla"`` instead.  f64 ranks are supported off-TPU only (the MXU
has no f64 path) — see docs/ENGINES.md.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import faults as flt
from repro.core import frontier as fr
from repro.core.blocked import SweepStats
from repro.core.graph import GraphSnapshot
from repro.kernels.block_spmv import ops


def build_pull_matrix(g: GraphSnapshot, dtype=np.float64,
                      padded: bool = False) -> ops.BlockSparse:
    """Block-sparse pull matrix for a snapshot: A[v, u] = 1 iff edge u→v
    (self-loops included), padded to the snapshot's block grid so row-blocks
    coincide with the engine's vertex blocks.  ``padded=True`` preallocates
    the tile pool / slot tables on the growth ladder (streaming layout)."""
    src, dst = g.in_edges_host()
    return ops.build_block_sparse(dst, src, g.n_pad, g.n_pad,
                                  block=g.block_size, dtype=dtype,
                                  padded=padded)


@partial(jax.jit, static_argnames=("n", "block_size", "mode", "expand",
                                   "active_policy", "max_iterations",
                                   "interpret", "backend", "tiered"))
def _driver(mat: ops.BlockSparse, R0, affected0, valid, out_deg,
            rb_in, rb_out, bmat, rb_res, alpha, tau, tau_f,
            part_table, alive_table, delay_table, crashed_any, *,
            n: int, block_size: int, mode: str, expand: bool,
            active_policy: str, max_iterations: int, interpret: bool,
            backend: str, tiered: bool = False):
    """The fused loop.  Returns (ranks [n_pad], stats vector [7],
    deferred row-block indicator [n_rb]).

    Every operand keeps a stable shape across a dynamic stream (the pull
    matrix is capacity-padded; the degree/adjacency vectors are per-block,
    the grid is fixed), so a stream re-enters one compiled trace.

    A sweep's three phases run under ``jax.named_scope`` (``df.sweep``,
    ``df.expand``, ``df.account``), which names them in the ops' metadata
    and changes nothing that runs.

    ``tiered=True`` (tiered storage, :mod:`repro.core.tiering`): ``mat`` is
    the device *hot-slab view* and ``rb_res`` marks which row-blocks are
    resident.  A non-resident block is never swept — seeds landing in it and
    expansion candidates touching it are recorded in the ``deferred``
    indicator instead (the whole block is re-marked, mirroring the helping
    mechanism: another drive picks the work up after admission, with **no
    mid-sweep host sync**).  The caller loops admit(deferred) → re-drive
    until the indicator is empty.  Untiered callers pass ``rb_res`` all-True
    and get an all-False indicator back.
    """
    dtype = R0.dtype
    B = block_size
    n_pad = valid.shape[0]
    n_rb = n_pad // B
    jacobi = mode == "bb"
    # counters accumulate in float: f64 (x64 on) is integer-exact to 2^53;
    # without x64 an int32 would wrap past 2^31 edges whereas f32 degrades
    # gracefully (and the returned stats vector is f32 there anyway)
    cdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    ladder = ops.active_ladder(n_rb)

    deg = jnp.maximum(out_deg, 1).astype(dtype)
    inv_deg = jnp.where(valid, 1.0 / deg, 0).astype(dtype)
    base = ((1.0 - alpha) / n).astype(dtype)
    alpha_c = alpha.astype(dtype)
    tau_c = tau.astype(dtype)
    tau_f_c = tau_f.astype(dtype)
    n_threads = part_table.shape[1]

    R = jnp.where(valid, R0[:n_pad], 0).astype(dtype)
    affected = affected0[:n_pad] & valid
    if tiered:
        # seeds in non-resident blocks are deferred wholesale before the loop
        res_v = jnp.repeat(rb_res, B)
        deferred0 = fr.block_any(affected & ~res_v, n_rb, B)
        affected = affected & res_v
    else:
        deferred0 = jnp.zeros((n_rb,), bool)
    RC = affected

    def cond(state):
        (_, _, _, it, converged, dnf, _, _) = state
        return ~converged & ~dnf & (it < max_iterations)

    def body(state):
        R, affected, RC, it, converged, dnf, deferred, ctr = state
        act_flags = affected if active_policy == "affected" else RC
        act_rb = fr.block_any(act_flags, n_rb, B)
        n_act = act_rb.sum(dtype=jnp.int32)
        no_work = n_act == 0

        if jacobi:
            participate = jnp.ones((n_threads,), bool)
            crash_now = crashed_any[it] & ~no_work
            asleep = jnp.asarray(False)
        else:
            participate = part_table[it]
            crash_now = jnp.asarray(False)
            asleep = ~participate.any() & ~no_work
        do = ~no_work & ~crash_now & ~asleep

        # -- compacted frontier sweep: pull over active row-blocks only,
        #    launched at the smallest ladder bucket ≥ |active| -------------
        with jax.named_scope("df.sweep"):
            ids = jnp.where(do, fr.compact_block_ids(act_rb, n_rb), -1)
            n_eff = jnp.where(do, n_act, 0)
            pulled = ops.block_spmv_active_bucketed(
                mat, R * inv_deg, ids, n_eff, semiring="sum",
                interpret=interpret, backend=backend, ladder=ladder)
            r_new = base + alpha_c * pulled
            act_v = jnp.repeat(act_rb, B)
            upd = affected & act_v & valid & do
            r_fin = jnp.where(upd, r_new, R)
            dr = jnp.where(upd, jnp.abs(r_fin - R), 0)
            maxdr = dr.max()
            RC1 = jnp.where(upd, dr > tau_c, RC)

        # -- DF expansion: OR semiring over candidate row-blocks ------------
        with jax.named_scope("df.expand"):
            if expand:
                changed = upd & (dr > tau_f_c)
                ch_cb = fr.block_any(changed, n_rb, B)
                cand_rb = (bmat & ch_cb[None, :]).any(axis=1)
                if tiered:
                    # candidate blocks not on device: defer (re-mark for the
                    # next drive after admission) instead of syncing mid-sweep
                    deferred = deferred | (cand_rb & ~rb_res & do)
                    cand_rb = cand_rb & rb_res
                n_cand = jnp.where(do, cand_rb.sum(dtype=jnp.int32), 0)
                cids = jnp.where(do, fr.compact_block_ids(cand_rb, n_rb), -1)
                hitf = ops.block_spmv_active_bucketed(
                    mat, changed.astype(dtype), cids, n_cand, semiring="or",
                    interpret=interpret, backend=backend, ladder=ladder)
                hit = (hitf > 0) & jnp.repeat(cand_rb, B) & valid & do
                affected1 = affected | hit
                RC1 = RC1 | hit
                out_rb = jnp.where(ch_cb, rb_out, 0)
            else:
                affected1 = affected
                ch_cb = jnp.zeros((n_rb,), bool)
                out_rb = jnp.zeros((n_rb,), rb_out.dtype)

        # -- work accounting + fault-time model (paper §5.1.6) --------------
        with jax.named_scope("df.account"):
            in_rb = jnp.where(act_rb, rb_in, 0)
            e_sweep = jnp.where(do, (in_rb + out_rb).astype(cdt).sum(), 0)
            ids_c = jnp.maximum(ids, 0)
            real_slot = ids >= 0
            slot_edges = jnp.where(
                real_slot,
                rb_in[ids_c] + jnp.where(ch_cb[ids_c], rb_out[ids_c], 0),
                0).astype(jnp.float32)
            pid = jnp.nonzero(participate, size=n_threads,
                              fill_value=0)[0].astype(jnp.int32)
            w = participate.sum(dtype=jnp.int32)
            tid = pid[jnp.arange(n_rb, dtype=jnp.int32) % jnp.maximum(w, 1)]
            th_edges = jax.ops.segment_sum(slot_edges, tid,
                                           num_segments=n_threads)
            th_blocks = jax.ops.segment_sum(real_slot.astype(jnp.float32), tid,
                                            num_segments=n_threads)
            work_ms = (th_edges * flt.T_EDGE_NS
                       + th_blocks * flt.T_BLOCK_NS) * 1e-6
            delay_row = delay_table[it]
            alive = alive_table[it]
            if jacobi:
                step_ms = jnp.max(work_ms + delay_row)
            else:
                step_ms = jnp.where(
                    asleep, jnp.max(jnp.where(alive, delay_row, 0)),
                    jnp.max(jnp.where(alive, work_ms, 0)))
            step_ms = jnp.where(do | asleep, step_ms, 0.0)

        # -- convergence ----------------------------------------------------
        if jacobi:
            conv_after = do & (maxdr <= tau_c)
        else:
            # RC-empty is the paper's LF criterion; the maxdr escape stops
            # a float limit cycle: when τ_f sits below the ulp floor, a
            # period-2 fixed point jitters forever above τ_f and the
            # expansion re-marks RC every sweep even though no vertex has
            # moved more than τ — abandoning that sub-τ wave inflates the
            # error by at most τ·α/(1−α), the paper's own stability bound
            conv_after = do & ((maxdr <= tau_c) | ~(RC1 & valid).any())
        converged1 = converged | no_work | conv_after
        dnf1 = dnf | crash_now

        sweeps, iters, blocks, edges, sim = ctr
        ctr1 = (sweeps + jnp.where(do | asleep, 1, 0).astype(cdt),
                iters + jnp.where(do, 1, 0).astype(cdt),
                blocks + jnp.where(do, n_act, 0).astype(cdt),
                edges + e_sweep,
                sim + step_ms.astype(jnp.float32))
        return (r_fin, affected1, RC1, it + 1, converged1, dnf1, deferred,
                ctr1)

    zero = jnp.zeros((), cdt)
    init = (R, affected, RC, jnp.int32(0), jnp.asarray(False),
            jnp.asarray(False), deferred0,
            (zero, zero, zero, zero, jnp.zeros((), jnp.float32)))
    R, _, _, _, converged, dnf, deferred, ctr = lax.while_loop(
        cond, body, init)
    sweeps, iters, blocks, edges, sim = ctr
    fdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    stats = jnp.stack([sweeps.astype(fdt), iters.astype(fdt),
                       blocks.astype(fdt), edges.astype(fdt),
                       sim.astype(fdt), converged.astype(fdt),
                       dnf.astype(fdt)])
    return R, stats, deferred


def _stats_from_vec(sv: np.ndarray) -> SweepStats:
    return SweepStats(
        sweeps=int(sv[0]), iterations=int(sv[1]), blocks_processed=int(sv[2]),
        edges_processed=int(sv[3]), sim_time_ms=float(sv[4]),
        converged=bool(sv[5] > 0), dnf=bool(sv[6] > 0))


def run_pallas(g: GraphSnapshot, R0: jnp.ndarray, affected0: jnp.ndarray,
               *, mode: str = "lf", expand: bool = True,
               alpha: float = 0.85, tau: float = 1e-10,
               tau_f: Optional[float] = None, max_iterations: int = 500,
               faults: Optional[flt.FaultPlan] = None,
               active_policy: str = "affected",
               mat: Optional[ops.BlockSparse] = None,
               aux=None,
               interpret: Optional[bool] = None,
               backend: Optional[str] = None,
               ) -> Tuple[jnp.ndarray, SweepStats]:
    """Fused-engine entry point; signature mirrors ``blocked.run_blocked``.

    ``mat`` may be supplied (e.g. maintained incrementally across a dynamic
    stream via :class:`repro.core.incremental.IncrementalPullMatrix`);
    otherwise it is built from the snapshot.  ``aux`` may carry the cached
    per-block vectors (any object with ``bmat`` / ``rb_in`` / ``rb_out``
    attributes, e.g. ``IncrementalPullMatrix.aux``) so a stream avoids
    recomputing the tile-presence adjacency and block-degree vectors per
    call.  ``backend`` picks the tile-SpMV backend
    (:func:`repro.kernels.block_spmv.ops.default_backend` when None).  The
    convergence loop itself performs **zero** host synchronisations — the
    only transfer is the final (ranks, stats) fetch after the
    ``while_loop`` exits.
    """
    if mode not in ("lf", "bb"):
        raise ValueError(mode)
    if active_policy not in ("affected", "rc"):
        raise ValueError(active_policy)
    if tau_f is None:
        tau_f = tau / 1000.0 if expand else float("inf")
    if not expand:
        tau_f = float("inf")
    if interpret is None:
        interpret = ops.default_interpret()
    backend = ops._resolve_backend(backend)
    plan = faults or flt.NO_FAULTS
    dtype = R0.dtype
    if mat is None:
        mat = build_pull_matrix(g, dtype=np.dtype(dtype))
    elif mat.block != g.block_size or mat.n_rows != g.n_pad:
        raise ValueError(
            f"pull matrix grid (block={mat.block}, n_rows={mat.n_rows}) "
            f"does not match snapshot (block={g.block_size}, "
            f"n_pad={g.n_pad}); rebuild with build_pull_matrix")

    if aux is not None:
        rb_in, rb_out = jnp.asarray(aux.rb_in), jnp.asarray(aux.rb_out)
        bmat = jnp.asarray(aux.bmat)
    else:
        rb_in, rb_out = g.block_in_edges(), g.block_out_edges()
        bmat = ops.block_adjacency(mat)

    part, alive, delay, crashed = plan.device_tables(max_iterations)
    f = jnp.asarray
    rb_res = jnp.ones((mat.n_rb,), bool)    # untiered: everything resident
    R, stats_vec, _ = _driver(
        mat, R0[:g.n_pad], affected0[:g.n_pad], g.vertex_valid, g.out_deg,
        rb_in, rb_out, bmat, rb_res,
        f(alpha), f(tau), f(tau_f),
        f(part), f(alive), f(delay), f(crashed),
        n=g.n, block_size=g.block_size, mode=mode, expand=expand,
        active_policy=active_policy, max_iterations=max_iterations,
        interpret=interpret, backend=backend)
    sv = np.asarray(jax.block_until_ready(stats_vec))   # the single sync
    return R[:g.n_pad], _stats_from_vec(sv)


# ---------------------------------------------------------------------------
# repro.api engine adapter (Engine protocol; discovered lazily by
# repro.api.registry so this module never imports the api package)
# ---------------------------------------------------------------------------

class PallasEngine:
    """Registry adapter for the fused frontier engine.  ``mat`` / ``aux``
    carry the incrementally maintained pull matrix + per-block operands
    (:class:`repro.core.incremental.IncrementalPullMatrix`); ``backend``
    picks the tile-SpMV backend."""

    name = "pallas"
    fault_domains = ("thread", "process", "corruption")

    def run(self, g, R0, affected0, *, mode, expand, alpha, tau, tau_f,
            max_iterations, faults, tile, active_policy,
            mat=None, aux=None, backend=None, interpret=None, shards=None):
        from repro.api.registry import reject_shard_spec
        reject_shard_spec(self.name, shards)
        del tile    # blocked-engine knob; the fused driver launches tiles
        R, stats = run_pallas(
            g, R0, affected0, mode=mode, expand=expand, alpha=alpha,
            tau=tau, tau_f=tau_f, max_iterations=max_iterations,
            faults=faults, active_policy=active_policy, mat=mat, aux=aux,
            backend=backend, interpret=interpret)
        return jax.block_until_ready(R), stats


def as_engine() -> PallasEngine:
    return PallasEngine()
