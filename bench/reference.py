"""Plain PageRank reference, independent of the program under test.

The semantics the configurations state (the paper's, §5.1.3): every vertex
carries a self-loop, so no vertex dangles and no teleport correction is
needed; ``r = (1 - alpha) / n + alpha * A r / outdeg``, where ``A[v, u] = 1``
for each edge ``u -> v`` and ``outdeg`` counts the self-loop.  Power
iteration from the uniform vector, in float64, for a fixed number of
iterations: at alpha = 0.85, 200 iterations leave an error of
0.85**200 ~ 8e-15 of the start, far below any tolerance compared here.

``precision="bfloat16"`` is the control: each iteration's contributions
``r / outdeg`` are rounded to bfloat16 before the sum, as a matrix unit's
single bfloat16 pass would round them, with ranks kept in float32.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

PRECISIONS = ("float64", "bfloat16")


def pagerank(n: int, edges: np.ndarray, *, alpha: float,
             precision: str = "float64", iterations: int = 200
             ) -> np.ndarray:
    """Ranks [n] of the graph with edge list ``edges`` ([m, 2] src, dst;
    no self-loops), one self-loop per vertex added here."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    loops = np.arange(n, dtype=np.int64)
    src = np.concatenate([edges[:, 0], loops])
    dst = np.concatenate([edges[:, 1], loops])
    a = sp.csr_matrix((np.ones(len(src)), (dst, src)), shape=(n, n))
    deg = np.bincount(src, minlength=n).astype(np.float64)
    base = (1.0 - alpha) / n
    r = np.full(n, 1.0 / n)
    for _ in range(iterations):
        c = r / deg
        if precision == "bfloat16":
            c = c.astype(ml_dtypes.bfloat16).astype(np.float64)
            r = (base + alpha * (a @ c)).astype(np.float32).astype(
                np.float64)
        else:
            r = base + alpha * (a @ c)
    return r
