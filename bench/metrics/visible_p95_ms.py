"""visible_p95_ms: 95th percentile, over the update requests due in the
window and applied, of the time from each one's due time until the read
snapshot held it (open loop)."""
import numpy as np


def read(run):
    v = [r["visible"] - r["due"] for r in run["requests"] if "visible" in r]
    if run["loop"] != "open" or not v:
        return None
    return float(np.percentile(v, 95)) * 1e3
