"""Edge-update streams: uniform deletions of existing edges and uniform
insertions of non-edges, generated in one pass so that no edge appears in
two batches (paper §5.1.4's random batches, as a whole stream)."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

Batch = Tuple[np.ndarray, np.ndarray]       # (deletions, insertions), [k, 2]


def edge_stream(n: int, keys: np.ndarray, sizes: Sequence[Tuple[int, int]],
                rng: np.random.Generator, *, symmetric: bool = False
                ) -> List[Batch]:
    """Batches of ``(n_del, n_ins)`` edges each, in order.

    ``keys`` are the graph's sorted ``src * n + dst`` edge keys.  Deletions
    are drawn without replacement from those edges, insertions uniformly
    from the ordered pairs ``u != v`` that are not edges, all distinct.  No
    inserted edge was ever an edge, and no deleted edge is inserted again,
    so every batch is valid against the graph the batches before it left.

    Deletions are drawn by kind, each kind uniformly (:func:`_deletions`).

    ``symmetric``: the graph is undirected (each edge stored as both arcs)
    and so is each update: the sizes count undirected edges, drawn as pairs
    ``u < v``, and each batch carries both arcs of every edge."""
    keys = np.asarray(keys, np.int64)
    if symmetric:
        keys = keys[keys // n < keys % n]
    n_del = sum(d for d, _ in sizes)
    n_ins = sum(i for _, i in sizes)
    if n_del > len(keys):
        raise ValueError(f"{n_del} deletions from a graph of {len(keys)} "
                         "edges")
    del_keys = _deletions(n, keys, [d for d, _ in sizes], rng)
    ins_keys = np.zeros(0, np.int64)
    while len(ins_keys) < n_ins:
        want = 2 * (n_ins - len(ins_keys)) + 16
        u = rng.integers(0, n, want)
        v = rng.integers(0, n, want)
        if symmetric:
            u, v = np.minimum(u, v), np.maximum(u, v)
        cand = u[u != v] * np.int64(n) + v[u != v]
        pos = np.clip(np.searchsorted(keys, cand), 0, len(keys) - 1)
        cand = cand[keys[pos] != cand]
        merged = np.concatenate([ins_keys, cand])
        _, first = np.unique(merged, return_index=True)
        ins_keys = merged[np.sort(first)]
    ins_keys = ins_keys[:n_ins]

    out, di, ii = [], 0, 0
    for d, i in sizes:
        dk, ik = del_keys[di:di + d], ins_keys[ii:ii + i]
        d_arcs = np.stack([dk // n, dk % n], 1)
        i_arcs = np.stack([ik // n, ik % n], 1)
        out.append((_both(d_arcs), _both(i_arcs)) if symmetric
                   else (d_arcs, i_arcs))
        di, ii = di + d, ii + i
    return out


#: kinds of deletion drawn apart: edges whose smaller endpoint degree is
#: 1, 2, ..., and KINDS or more
KINDS = 4


def _deletions(n: int, keys: np.ndarray, n_dels: Sequence[int],
               rng: np.random.Generator) -> np.ndarray:
    """Distinct edges to delete, in batch order, ``n_dels[b]`` for batch b.

    What a deletion costs the solver follows the degree of its endpoints:
    deleting the last edge of a vertex changes its rank the most and costs
    about twice the sweeps of another deletion (about 1% of a Graph500
    graph's edges are a vertex's last).  A uniform draw would put a count
    of each kind into a window that varies from seed to seed, so the kinds
    (the smaller endpoint degree: 1, 2, ..., ``KINDS`` or more) are drawn
    apart: each batch takes each kind's share by cumulative rounding, the
    same counts at the same batches for every seed, and within a kind
    every edge is as likely as any other."""
    deg = np.bincount(np.concatenate([keys // n, keys % n]), minlength=n)
    kind = np.minimum(np.minimum(deg[keys // n], deg[keys % n]), KINDS) - 1
    pools = [rng.permutation(keys[kind == k]) for k in range(KINDS)]
    cum = np.cumsum([0, *n_dels])
    taken = np.zeros((KINDS, len(cum)), np.int64)
    for k in range(KINDS - 1):
        taken[k] = np.round(cum * len(pools[k]) / max(len(keys), 1))
    taken[-1] = cum - taken[:-1].sum(0)
    if (np.diff(taken, axis=1) < 0).any():
        raise ValueError("batches too small for the kinds of deletion")
    out = [rng.permutation(np.concatenate([
        pools[k][taken[k, b]:taken[k, b + 1]] for k in range(KINDS)]))
        for b in range(len(n_dels))]
    if [len(o) for o in out] != list(n_dels):
        raise ValueError("too few edges of one kind for the deletions")
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def widen_row(n: int, keys: np.ndarray, block: int, width: int,
              rng: np.random.Generator, *, symmetric: bool = False
              ) -> np.ndarray:
    """Keys with edges added into the row block that holds the most tiles
    (pull layout: a row block is ``block`` destinations, a tile one source
    block of them, the self-loops' diagonal tile counted), from random
    vertices of as many other source blocks as it takes for that row to
    hold ``width // 2 + 1`` tiles: the program's slot tables then start at
    ``width``, the width a stream of uniform insertions widens them to.
    ``symmetric`` adds each edge's other arc too."""
    keys = np.asarray(keys, np.int64)
    n_b = -(-n // block)
    tiles = np.unique(np.concatenate([
        (keys % n) // block * n_b + (keys // n) // block,
        np.arange(n_b, dtype=np.int64) * (n_b + 1)]))
    per_row = np.bincount(tiles // n_b, minlength=n_b)
    row = int(np.argmax(per_row))
    free = np.setdiff1d(np.arange(n_b), tiles[tiles // n_b == row] % n_b)
    need = min(width // 2 + 1 - int(per_row[row]), len(free))
    if need <= 0:
        return keys
    src = np.minimum(rng.choice(free, need, replace=False) * block
                     + rng.integers(0, block, need), n - 1)
    dst = np.minimum(row * block + rng.integers(0, block, need), n - 1)
    new = src * np.int64(n) + dst
    if symmetric:
        new = np.concatenate([new, dst * np.int64(n) + src])
    return np.union1d(keys, new)


def _both(arcs: np.ndarray) -> np.ndarray:
    """Each ``(u, v)`` followed by ``(v, u)``."""
    return np.stack([arcs, arcs[:, ::-1]], 1).reshape(-1, 2)


def apply_stream(keys: np.ndarray, n: int, batches: Sequence[Batch]
                 ) -> np.ndarray:
    """Sorted edge keys after ``batches`` are applied to ``keys``."""
    if not batches:
        return np.asarray(keys, np.int64)
    dels = np.concatenate([d[:, 0] * np.int64(n) + d[:, 1]
                           for d, _ in batches])
    ins = np.concatenate([i[:, 0] * np.int64(n) + i[:, 1]
                          for _, i in batches])
    kept = keys[~np.isin(keys, dels)]
    return np.union1d(kept, ins)
