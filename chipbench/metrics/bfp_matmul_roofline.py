"""Roofline share of the ``bfp_matmul_quantized`` kernel: over its
events in the traced steps, max(2MKN / peak, bytes / HBM bandwidth)
summed over the model's 1x1 layers at each step's padded batch, over
the summed event time."""
from chipbench.metrics._roofline import share


def read(win):
    return share(win, "bfp_matmul_quantized")
