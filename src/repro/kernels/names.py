"""How each Pallas kernel is named in a device trace.

A profiler trace of a TPU prints every operation as its HLO text: its
instruction name, then its operands *by instruction name*. So a kernel
named ``csd_spmm_fwd`` would lend that name to every XLA op that consumes
its output (``fusion(... %csd_spmm_fwd.3)``), and a reduction that finds a
kernel by name would count those ops as the kernel. Each kernel therefore
carries two names:

* its **kernel name** (``csd_spmm_fwd``, ``paged_decode_attention``, ...)
  in the custom call's kernel metadata, which only the kernel's own event
  prints;
* an **instruction name** that contains no kernel name (``junction_fwd``,
  ``paged_attention``, ...), which its consumers print.
"""
from __future__ import annotations

# kernel name -> instruction name
KERNELS = {
    "csd_spmm_fwd": "junction_fwd",
    "csd_spmm_fwd_batched": "junction_fwd_batched",
    "csd_spmm_fwd_int8": "junction_fwd_int8",
    "csd_spmm_fwd_int8_batched": "junction_fwd_int8_batched",
    "csd_spmm_dx": "junction_dx",
    "csd_spmm_dx_batched": "junction_dx_batched",
    "csd_spmm_dw": "junction_dw",
    "csd_spmm_dw_batched": "junction_dw_batched",
    "paged_decode_attention": "paged_attention",
    "flash_attention": "flash_attention",
}


def pallas_names(kernel: str) -> dict:
    """``pl.pallas_call`` keywords that name ``kernel`` in a trace."""
    return {"name": KERNELS[kernel], "metadata": {"kernel": kernel}}
