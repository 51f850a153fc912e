"""Roofline arithmetic of the tile SpMV, kept with the benchmark.

The work of one pull or frontier sweep is the edges it processes, and each
edge needs a 4-byte source index and a 4-byte rank read: 8 bytes.  That is
the work itself, not this implementation's dense tiles, so the share reads
the same whatever storage format a later change uses.  PageRank does about
2 flops per edge, against the v5e's 240 flops per HBM byte, so the bound is
the bytes: the least time is ``8 * edges / peak bytes per second``.
"""
from __future__ import annotations

import json
import os

BYTES_PER_EDGE = 8
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def spmv_roofline_pct(edges: float, kernel_s: float, peak: dict) -> float:
    """Percent of the HBM roofline: (8 B x edges / peak bandwidth) over
    the kernels' device seconds."""
    if kernel_s <= 0:
        raise ValueError(f"kernel time {kernel_s} s is not positive")
    return 100.0 * BYTES_PER_EDGE * edges / peak["hbm_bytes_per_s"] \
        / kernel_s
