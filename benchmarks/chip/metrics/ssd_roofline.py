"""Share (%) of its roofline that the SSD intra-chunk kernels (forward and
backward) reach in training: the larger of the operations over the bf16
peak and the bytes over HBM bandwidth that the chunked algorithm needs
(``chipbench/flops.py``), over the kernels' device time in the trace."""

from chipbench import flops


def read(ctx):
    return flops.kernel_roofline(ctx, "ssd")
