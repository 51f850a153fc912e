"""spmv_device_s_per_batch: device seconds of the tile SpMV kernels (both
semirings) in the traced window, per update batch."""
from bench.kernels import SPMV_KERNELS


def read(run):
    tr, b = run["trace"], run["batches"]
    if tr is None or run["loop"] != "closed" or not b:
        return None
    s = tr.kernel_s(SPMV_KERNELS)
    return s / len(b) if s > 0 else None
