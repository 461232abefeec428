"""Share (%) of its roofline that the flash-attention kernels (forward, dq,
dk/dv) reach in training: the larger of the operations over the bf16 peak
and the bytes over HBM bandwidth that the causal calls need
(``chipbench/flops.py``), over the kernels' device time in the trace."""

from chipbench import flops


def read(ctx):
    return flops.kernel_roofline(ctx, "flash_attention")
