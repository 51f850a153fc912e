"""The comparison that decides ``correct``.

Three numbers, each against a limit of its own (``limits/<cell>.json``):

``rank_err_tau``
    max over vertices of |program rank - reference rank|, in units of the
    configuration's tau: the tile SpMV kernel and the fused driver, through
    the final rank vector the timed path left.
``graph_diff``
    edges in which the graph the session ends on differs from the
    generator's final edge set (initial edges, minus every applied
    deletion, plus every applied insertion): the session's delta planning.
``read_err_tau``
    the widest gap, in units of tau, between what the read path answered
    after the window and the reference: each ``query`` value against the
    reference rank of its vertex; each ``top_k`` value against the
    reference rank of the vertex returned with it, and against the
    reference's own k largest ranks, position by position.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def read_gap(ref: np.ndarray, reads: List[Tuple[str, np.ndarray,
                                                 np.ndarray]]) -> float:
    """Widest gap of the ``(op, vertices, values)`` reads against ``ref``;
    for ``top_k`` the vertices are those the read returned."""
    worst = 0.0
    top = np.sort(ref)[::-1]
    for op, ids, vals in reads:
        ids = np.asarray(ids, np.int64)
        vals = np.asarray(vals, np.float64)
        if len(ids) != len(vals) or len(vals) == 0 \
                or (ids < 0).any() or (ids >= len(ref)).any() \
                or not np.isfinite(vals).all():
            return float("inf")
        worst = max(worst, float(np.abs(vals - ref[ids]).max()))
        if op == "top_k":
            worst = max(worst, float(np.abs(np.sort(vals)[::-1]
                                            - top[:len(vals)]).max()))
    return worst


def numbers(*, ranks: np.ndarray, ref: np.ndarray, tau: float,
            session_keys: np.ndarray, expected_keys: np.ndarray,
            reads: List[Tuple[str, np.ndarray, np.ndarray]]
            ) -> Dict[str, float]:
    n = len(ref)
    r = np.asarray(ranks, np.float64)[:n]
    err = float(np.abs(r - ref).max()) if np.isfinite(r).all() \
        else float("inf")
    return {
        "rank_err_tau": err / tau,
        "graph_diff": float(len(np.setxor1d(session_keys, expected_keys))),
        "read_err_tau": read_gap(ref, reads) / tau,
    }


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and each number beside its limit.  A number with no
    limit, or a limit with no number, is not correct."""
    checks = {k: {"value": nums.get(k), "limit": limits.get(k)}
              for k in sorted(set(nums) | set(limits))}
    ok = all(c["value"] is not None and c["limit"] is not None
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
