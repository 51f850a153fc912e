"""read_p95_ms: 95th percentile, over the query / top_k reads due in the
window, of the time from each one's due time until its values were on the
host (open loop)."""
import numpy as np


def read(run):
    v = [r["done"] - r["due"] for r in run["reads"] if "done" in r]
    if not v:
        return None
    return float(np.percentile(v, 95)) * 1e3
