"""spmv_roofline.serve: the tile SpMV's share of its HBM roofline over the
traced window of the open loop, over all the service's dispatches:
(8 B x edges processed / peak bandwidth) over the kernels' device
seconds."""
from bench.kernels import SPMV_KERNELS
from bench.roofline import spmv_roofline_pct


def read(run):
    tr, b = run["trace"], run["batches"]
    if tr is None or run["loop"] != "open" or not b:
        return None
    s = tr.kernel_s(SPMV_KERNELS)
    if s <= 0:
        return None
    return spmv_roofline_pct(sum(x["edges"] for x in b), s, run["peak"])
