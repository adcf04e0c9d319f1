"""The VMEM one Pallas grid step may hold.

TPU cores carry about 16 MiB of VMEM; Mosaic needs headroom for its
semaphores and metadata, so a step's double-buffered blocks get half. The
forward's tiling (``csd_spmm.fwd_tiling``) sizes its steps to this budget,
and sparselint's SL104 certifies every kernel against it.
"""
VMEM_BUDGET = 8 * 1024 * 1024
