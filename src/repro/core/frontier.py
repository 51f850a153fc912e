"""Dynamic Frontier (DF) marking — paper §4.1, and DT reachability marking.

All marking is expressed as idempotent OR-scatters / OR-SpMVs, which is what
makes the paper's helping mechanism race-free; the same property makes our
re-execution-based fault recovery exact.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.graph import GraphSnapshot, out_neighbor_or


def pack_batch(n_pad: int, deletions: np.ndarray, insertions: np.ndarray,
               *, bucket: int = 1024) -> jnp.ndarray:
    """Pack a batch update into a padded [b_pad, 2] i32 device array.
    Padded rows use the phantom vertex ``n_pad`` as source.  Snapshot-free
    variant for the streaming runtime (only the pad size is needed)."""
    b = np.concatenate([np.asarray(deletions, np.int64).reshape(-1, 2),
                        np.asarray(insertions, np.int64).reshape(-1, 2)], 0)
    b_pad = max(bucket, ((len(b) + bucket - 1) // bucket) * bucket)
    out = np.full((b_pad, 2), n_pad, dtype=np.int32)
    if len(b):
        out[:len(b)] = b
    return jnp.asarray(out)


def batch_to_device(g: GraphSnapshot, deletions: np.ndarray,
                    insertions: np.ndarray, *, bucket: int = 1024
                    ) -> jnp.ndarray:
    """Snapshot-keyed convenience wrapper around :func:`pack_batch`."""
    return pack_batch(g.n_pad, deletions, insertions, bucket=bucket)


def update_sources_indicator(g: GraphSnapshot, batch: jnp.ndarray
                             ) -> jnp.ndarray:
    """Indicator [n_pad] of source vertices appearing in the batch update."""
    ind = jnp.zeros((g.n_pad + 1,), dtype=bool)
    ind = ind.at[jnp.minimum(batch[:, 0], g.n_pad)].set(True)
    return ind[:g.n_pad] & g.vertex_valid


def initial_affected(g_prev: GraphSnapshot, g_cur: GraphSnapshot,
                     batch: jnp.ndarray) -> jnp.ndarray:
    """Paper lines 4-6 (Alg. 1): mark out-neighbors of every update source in
    both G^{t-1} and G^t.  Sources themselves are *not* marked."""
    ind_prev = update_sources_indicator(g_prev, batch)
    ind_cur = update_sources_indicator(g_cur, batch)
    aff = out_neighbor_or(g_prev, ind_prev) | out_neighbor_or(g_cur, ind_cur)
    return aff & g_cur.vertex_valid


def initial_affected_with_helping(
        g_prev: GraphSnapshot, g_cur: GraphSnapshot, batch: jnp.ndarray,
        first_pass_mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Fault-tolerant phase-1 marking with the paper's *helping* mechanism
    (Alg. 2 lines 5-16).

    ``first_pass_mask`` [b_pad] simulates which update edges the (possibly
    delayed/crashed) first owners actually processed.  The helping loop then
    re-processes every update whose checked flag ``C`` is still 0 — idempotent
    OR-marking makes duplicated work harmless.  Returns (affected, C, rounds).
    """
    n_pad = g_cur.n_pad
    real = batch[:, 0] < n_pad

    def mark(subset_mask: jnp.ndarray) -> jnp.ndarray:
        sub = jnp.where(subset_mask[:, None], batch,
                        jnp.full_like(batch, n_pad))
        return initial_affected(g_prev, g_cur, sub)

    affected = mark(first_pass_mask & real)
    C = (first_pass_mask & real) | ~real   # padded rows count as checked

    # helping rounds: any thread observing C[u]=0 re-processes that update
    rounds = 0
    # one helping round suffices functionally (survivors process everything
    # left); loop kept to mirror the paper's "while true ... all marked?"
    while bool((~C).any()):
        remaining = ~C
        affected = affected | mark(remaining)
        C = C | remaining
        rounds += 1
    return affected, C, rounds


def dt_affected(g_prev: GraphSnapshot, g_cur: GraphSnapshot,
                batch: jnp.ndarray, *, max_hops: int = 0) -> jnp.ndarray:
    """Dynamic Traversal marking (Alg. 7): everything *reachable* in G^t from
    the out-neighbors of update sources.  BFS as iterated OR-SpMV."""
    frontier = initial_affected(g_prev, g_cur, batch)
    affected = frontier
    hops = max_hops or g_cur.n_blocks * g_cur.block_size

    def cond(state):
        frontier, affected, i = state
        return jnp.logical_and(frontier.any(), i < hops)

    def body(state):
        frontier, affected, i = state
        new = out_neighbor_or(g_cur, frontier) & ~affected
        return new, affected | new, i + 1

    _, affected, _ = jax.lax.while_loop(
        cond, body, (frontier, affected, jnp.int32(0)))
    return affected


def block_any(flags: jnp.ndarray, n_blocks: int, block_size: int
              ) -> jnp.ndarray:
    """Per-block OR over a [n_pad] vertex indicator → [n_blocks] bool.
    Shared by the blocked engine's compaction and the fused Pallas driver."""
    return flags[:n_blocks * block_size].reshape(n_blocks,
                                                 block_size).any(axis=1)


def compact_block_ids(act: jnp.ndarray, n_blocks: int) -> jnp.ndarray:
    """Compacted active-block slot list: active ids first, then -1 padding.
    jit-safe (static length); the Pallas kernels launch over this list.

    Computed in int32 whatever the x64 setting: on a TPU a 64-bit prefix
    sum over 16K blocks is emulated with pairs of u32 and overflows the
    vector memory of its compile."""
    pos = jnp.where(act, jnp.cumsum(act, dtype=jnp.int32) - 1, n_blocks)
    ids = jnp.full((n_blocks + 1,), -1, jnp.int32)
    return ids.at[pos].set(jnp.arange(n_blocks, dtype=jnp.int32))[:n_blocks]


def expand_frontier(g: GraphSnapshot, changed: jnp.ndarray,
                    affected: jnp.ndarray, rc: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paper lines 15-17 (Alg. 1) / 25-28 (Alg. 2): mark out-neighbors of
    vertices whose rank moved more than τ_f; dense OR-SpMV form (the blocked
    engine does the same per-block with edge-proportional work)."""
    hit = out_neighbor_or(g, changed)
    return affected | hit, rc | hit
