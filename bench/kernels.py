"""Names under which the program's kernels appear in a device trace.

The tile SpMV kernel (``kernels/block_spmv``) carries no ``name=`` of its
own.  A TPU trace shows each launch as an HLO custom call,
``%tpu_custom_call.<k>``, with empty kernel metadata (read off a trace of
a Graph500 cell at scale 16 by hand).  It is the program's only Pallas
kernel, and both semirings, the pull sum and the frontier OR, launch it.
"""
SPMV_KERNELS = ("%tpu_custom_call",)
