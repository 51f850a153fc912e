"""Incremental maintenance of dynamic-graph state (beyond-paper).

Two members:

* :class:`IncrementalPullMatrix` — keeps the fused Pallas engine's
  block-sparse pull matrix in sync with a dynamic edge stream by patching
  only the tiles each batch touches (``ops.apply_delta``), instead of the
  O(m) host rebuild per snapshot.  This is the state carrier that makes the
  ``engine="pallas"`` path incremental end-to-end: frontier-proportional
  *compute* per sweep and batch-proportional *build* per snapshot.

* ``incremental_gnn_update`` — DF generalized to GNN vertex programs
  (DESIGN.md §Arch-applicability): after a batch of edge updates only nodes
  whose embeddings can change are re-embedded, with τ_f cutting off the
  receptive-field cone.  Gated on the model zoo being importable (the GNN
  stack needs :mod:`repro.dist`, which some builds omit).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.delta import signed_edge_delta
from repro.core.graph import GraphSnapshot, HostGraph
from repro.kernels.block_spmv import ops

try:  # the GNN family needs the dist substrate; PageRank paths do not
    from repro.models.gnn.common import GNNConfig, GraphBatch
    HAVE_GNN = True
except ImportError:  # pragma: no cover - depends on build flavor
    GNNConfig = GraphBatch = None
    HAVE_GNN = False


def effective_batch(hg_prev: HostGraph, deletions: np.ndarray,
                    insertions: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Filter a raw (deletions, insertions) batch down to the edges that
    actually change the graph, mirroring :meth:`HostGraph.apply_batch`
    exactly: dedupe, drop self-loops, deletions of absent edges are no-ops,
    insertions land in (prev − dels) — so an edge deleted and re-inserted
    in one batch nets to zero."""
    n = np.int64(hg_prev.n)

    def uniq(e):
        e = np.asarray(e, np.int64).reshape(-1, 2)
        e = e[e[:, 0] != e[:, 1]]
        k = np.unique(e[:, 0] * n + e[:, 1])
        return np.stack([k // n, k % n], 1), k

    dels, del_keys = uniq(deletions)
    ins, ins_keys = uniq(insertions)
    dels = dels[hg_prev.has_edges(dels)] if len(dels) else dels
    if len(ins):
        present = hg_prev.has_edges(ins)
        redeleted = np.isin(ins_keys, del_keys) if len(del_keys) else \
            np.zeros(len(ins), bool)
        ins = ins[~present | (present & redeleted)]
    return dels, ins


@dataclasses.dataclass
class MatrixAux:
    """Per-block engine operands cached alongside the pull matrix so a
    stream never recomputes them from scratch per ``run_pallas`` call:

    * ``bmat``   — tile-presence adjacency [n_rb, n_cb] (candidate-block
      selection for the OR-pass); monotone under deltas.
    * ``rb_in``  — in-edge count per dst-block (sweep work metric), equal
      to ``GraphSnapshot.block_in_edges()`` of the current graph.
    * ``rb_out`` — out-edge count per src-block (expansion work metric).

    All three update in O(batch) from the signed delta coordinates.
    ``bmat`` is patched *in place* (it is [n_rb, n_cb]: 268 MB at a
    16,384-block grid, against the batch's handful of cells); every
    device array built from it is an explicit copy
    (``jnp.array(..., copy=True)``), so no device mirror shares its
    buffer, and :meth:`PageRankSession.fork` copies it for the branch.
    A fork taken concurrently with a patch (the service's read-snapshot
    refresh runs outside the slot lock) copies the old map plus some of
    the batch's new cells: presence only ever turns cells on, and read
    snapshots serve ranks only, never consulting ``bmat``.
    """
    bmat: np.ndarray     # [n_rb, n_cb] bool
    rb_in: np.ndarray    # [n_rb] i32
    rb_out: np.ndarray   # [n_rb] i32

    @classmethod
    def from_parts(cls, mat: ops.BlockSparse, g: GraphSnapshot
                   ) -> "MatrixAux":
        return cls(bmat=np.asarray(ops.block_adjacency(mat)).copy(),
                   rb_in=np.asarray(g.block_in_edges()).copy(),
                   rb_out=np.asarray(g.block_out_edges()).copy())

    def apply_delta(self, block: int, rows: np.ndarray, cols: np.ndarray,
                    vals: np.ndarray) -> None:
        """O(batch) update from signed pull-layout coordinates (rows = dst,
        cols = src, vals = ±1): block degrees move by the signed counts
        (``rb_in``/``rb_out`` are rebound, [n_rb] each); tile presence ORs
        in every touched pair, in place."""
        if len(rows) == 0:
            return
        rb = np.asarray(rows, np.int64) // block
        cb = np.asarray(cols, np.int64) // block
        v = np.asarray(vals)
        n_rb = self.rb_in.shape[0]
        self.rb_in = self.rb_in + np.bincount(
            rb, weights=v, minlength=n_rb).astype(self.rb_in.dtype)
        self.rb_out = self.rb_out + np.bincount(
            cb, weights=v, minlength=n_rb).astype(self.rb_out.dtype)
        self.bmat[rb, cb] = True


class IncrementalPullMatrix:
    """Block-sparse pull matrix maintained incrementally across snapshots.

    Usage along a dynamic stream::

        inc = IncrementalPullMatrix.from_snapshot(g0, dtype=np.float64)
        ...
        hg1 = hg0.apply_batch(dels, ins)
        g1 = hg1.snapshot(...)
        mat1 = inc.advance(hg0, g1, dels, ins)   # patches touched tiles only
        res = df_pagerank(g0, g1, batch, r, engine="pallas",
                          pallas_mat=mat1, pallas_aux=inc.aux)

    ``advance`` filters the batch against the previous host graph the same
    way :meth:`HostGraph.apply_batch` does (:func:`effective_batch`), so
    tile values track edge multiplicity exactly; self-loops never change
    (every vertex always has one).  Structure grows monotonically — emptied
    tiles stay as zero blocks — so a delete+reinsert round-trip reproduces
    the original matrix values exactly (the paper's §5.2.3 stability
    property, at build level).

    The per-block engine operands (tile-presence adjacency + block-degree
    vectors, :class:`MatrixAux`) are cached and patched per batch instead
    of being recomputed per ``run_pallas`` call; ``padded=True`` (the
    default) builds the matrix capacity-padded so delta batches keep
    ``tiles.shape`` / ``max_tiles`` stable — the recompile-free streaming
    layout (see :mod:`repro.core.stream`).
    """

    def __init__(self, mat: ops.BlockSparse, aux: Optional[MatrixAux] = None):
        self.mat = mat
        self.aux = aux

    @classmethod
    def from_snapshot(cls, g: GraphSnapshot, dtype=np.float64,
                      padded: bool = True) -> "IncrementalPullMatrix":
        from repro.core.pallas_engine import build_pull_matrix
        mat = build_pull_matrix(g, dtype=dtype, padded=padded)
        return cls(mat, MatrixAux.from_parts(mat, g))

    def advance(self, hg_prev: HostGraph, g_new: Optional[GraphSnapshot],
                deletions: np.ndarray, insertions: np.ndarray, *,
                effective: Optional[Tuple[np.ndarray, np.ndarray]] = None
                ) -> ops.BlockSparse:
        """Patch the matrix (and cached aux) with one edge batch.  ``g_new``
        is only consulted for the grid check and may be None on a stream
        (the grid is fixed; out-of-range coordinates are rejected by
        ``ops.apply_delta`` regardless).  ``effective`` may carry an
        already-filtered (dels, ins) pair so callers that need the
        filtered batch themselves don't run :func:`effective_batch`
        twice."""
        if g_new is not None and g_new.n_pad > self.mat.n_rows:
            raise ValueError("snapshot outgrew the matrix block grid; "
                             "rebuild with from_snapshot")
        dels, ins = (effective if effective is not None
                     else effective_batch(hg_prev, deletions, insertions))
        rows, cols, vals = signed_edge_delta(dels, ins)
        self.mat = ops.apply_delta(self.mat, rows, cols, vals)
        if self.aux is not None:
            self.aux.apply_delta(self.mat.block, rows, cols, vals)
        return self.mat


def edge_update_sources(n_pad: int, deletions: np.ndarray,
                        insertions: np.ndarray) -> jnp.ndarray:
    """Indicator of update source vertices (both endpoints for undirected
    message passing: a changed edge changes BOTH endpoints' aggregations)."""
    ind = np.zeros(n_pad + 1, dtype=bool)
    for batch in (deletions, insertions):
        b = np.asarray(batch, np.int64).reshape(-1, 2)
        ind[np.minimum(b[:, 0], n_pad)] = True
        ind[np.minimum(b[:, 1], n_pad)] = True
    return jnp.asarray(ind[:n_pad])


def out_neighbors_or(g: GraphBatch, flags: jnp.ndarray) -> jnp.ndarray:
    """Nodes receiving at least one message from a flagged node."""
    f = jnp.concatenate([flags, jnp.zeros((1,), flags.dtype)])
    hit = jax.ops.segment_max(
        f[jnp.minimum(g.senders, g.n_pad)].astype(jnp.int32),
        g.receivers, num_segments=g.n_pad + 1)[:g.n_pad]
    return hit > 0


def incremental_gnn_update(
        layer_fns, g: GraphBatch, h0: jnp.ndarray,
        cached_layers, sources: jnp.ndarray, *, tau_f: float
) -> Tuple[jnp.ndarray, list, Dict[str, int]]:
    """Recompute a layered GNN after a graph update, DF-style.

    layer_fns[i](g, h) -> h'  — full-graph layer functions;
    cached_layers[i]          — pre-update activations per layer (i=0 input);
    sources                   — indicator of update-source nodes.

    Per layer: recompute only currently-affected nodes (others keep their
    cached activation), then expand the frontier to the out-neighbors of
    nodes whose activation moved more than τ_f — the DF gate.  Returns the
    new final activations, the refreshed cache, and work counters.
    """
    affected = out_neighbors_or(g, sources) | sources
    new_cache = [h0]
    h = h0
    stats = {"recomputed": 0, "total": 0}
    for i, fn in enumerate(layer_fns):
        full = fn(g, h)                       # masked cost model: a real
        # deployment computes only affected rows; on TPU the win is measured
        # in the affected-row count (stats) while XLA computes dense tiles.
        prev = cached_layers[i + 1]
        h_new = jnp.where(affected[:, None], full, prev)
        moved = affected & (
            jnp.max(jnp.abs(h_new - prev), axis=-1) > tau_f)
        stats["recomputed"] += int(affected.sum())
        stats["total"] += int(g.n_pad)
        affected = affected | out_neighbors_or(g, moved)
        new_cache.append(h_new)
        h = h_new
    return h, new_cache, stats


def full_gnn_layers(mod, params, cfg: GNNConfig):
    """Adapt a model-zoo family into per-layer closures for the incremental
    path (graphsage-style: h' = layer(h))."""
    if cfg.family != "graphsage":
        raise NotImplementedError(
            "incremental path is exercised on graphsage (mean aggregation "
            "is layer-local); other families need their edge state threaded")
    from repro.models.gnn import graphsage as GS
    from repro.models.gnn import common as C

    def make(i):
        def fn(g, h):
            neigh = C.scatter_mean(g, C.gather_src(g, h))
            return GS._layer(params, i, h, neigh)
        return fn

    return [make(i) for i in range(cfg.n_layers)]
