"""The benchmark's own graph generators, kept apart from the program's.

A copy of ``grid_road`` as the program had it when the benchmark was
defined, returning plain edge arrays, and ``graph500``, the Graph500
specification's generator (the program's ``rmat`` with ids scrambled and
edges undirected, as the specification asks): the yardstick must not move
when the program's generators change.  Each returns ``(n, edges)``:
an ``[m, 2] int64`` array of distinct ``(src, dst)`` pairs with no
self-loops, sorted by ``src * n + dst``.

A further generator needs no edit here: a configuration whose ``graph`` is
not in :data:`GENERATORS` names a file ``graphs/<graph>.py`` whose function
``<graph>`` takes its parameters by name and ``seed`` by keyword, and
returns ``(n, edges)`` as these do.
"""
from __future__ import annotations

import importlib.util
import inspect
import os
import re
from typing import Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def edge_keys(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Sorted unique ``src * n + dst`` keys of the non-loop edges."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    keep = src != dst
    return np.unique(src[keep] * np.int64(n) + dst[keep])


def keys_to_edges(n: int, keys: np.ndarray) -> np.ndarray:
    return np.stack([keys // n, keys % n], axis=1)


def grid_road(side: int, *, diag_frac: float = 0.05, seed: int
              ) -> Tuple[int, np.ndarray]:
    """2-D lattice, both directions, plus ``diag_frac * n`` random
    shortcuts: average out-degree about 4, like the DIMACS10 OSM graphs."""
    rng = np.random.default_rng(seed)
    n = side * side
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    vid = (ii * side + jj).ravel()
    right = vid[(jj < side - 1).ravel()]
    down = vid[(ii < side - 1).ravel()]
    e = [np.stack([right, right + 1], 1), np.stack([right + 1, right], 1),
         np.stack([down, down + side], 1), np.stack([down + side, down], 1)]
    k = int(diag_frac * n)
    if k:
        e.append(np.stack([rng.integers(0, n, k), rng.integers(0, n, k)], 1))
    e = np.concatenate(e)
    return n, keys_to_edges(n, edge_keys(n, e[:, 0], e[:, 1]))


def graph500(scale: int, edge_factor: int = 16, *, a: float = 0.57,
             b: float = 0.19, c: float = 0.19, seed: int
             ) -> Tuple[int, np.ndarray]:
    """Graph500 Kronecker graph (graph500.org specification, section 3):
    ``edge_factor * 2**scale`` undirected edges, one quadrant choice per
    level with probabilities a, b, c, d, vertex ids scrambled by a random
    permutation, and each edge stored as both of its arcs.  Duplicate
    edges and self-loops are dropped, as in a simple undirected graph;
    isolated vertices stay, so ``n = 2**scale``."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for level in range(scale):
        r = rng.random(m)
        right = r >= a + b
        down = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src |= down.astype(np.int64) << level
        dst |= right.astype(np.int64) << level
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    return n, keys_to_edges(n, edge_keys(n, np.concatenate([src, dst]),
                                         np.concatenate([dst, src])))


GENERATORS = {"grid_road": grid_road, "graph500": graph500}


def find_generator(graph: str, graphs_dir: str = HERE):
    """The generator a configuration names: one of :data:`GENERATORS`, or
    else the function ``<graph>`` of ``<graphs_dir>/<graph>.py``."""
    if graph in GENERATORS:
        return GENERATORS[graph]
    path = os.path.join(graphs_dir, graph + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no graph generator {graph!r}: neither one of "
                       f"{sorted(GENERATORS)} nor {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_graph_" + re.sub(r"\W", "_", graph), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, graph)


def make_graph(cfg: dict, seed, graphs_dir: str = HERE
               ) -> Tuple[int, np.ndarray]:
    """Build the graph a configuration names: ``cfg["graph"]`` is the
    generator (:func:`find_generator`), and its keyword parameters are the
    keys of ``cfg`` that carry their names."""
    gen = find_generator(cfg["graph"], graphs_dir)
    names = [p for p in inspect.signature(gen).parameters if p != "seed"]
    return gen(**{k: cfg[k] for k in names if k in cfg}, seed=seed)
