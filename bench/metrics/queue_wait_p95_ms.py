"""queue_wait_p95_ms: 95th percentile of UpdateRequest.wait_s (submitted to
dispatch start) over the window's applied requests, in the service."""
import numpy as np


def read(run):
    v = [r["started"] - r["submitted"] for r in run["requests"]
         if "started" in r]
    if run["loop"] != "open" or not v:
        return None
    return float(np.percentile(v, 95)) * 1e3
