"""Roofline share of the ``winograd_tile_matmul`` kernel: its own
36 * P * Cin * Cout multiply-adds and its V, U and output bytes, summed
over the model's 3x3 stride-1 layers at each step's padded batch, over
the summed event time."""
from chipbench.metrics._roofline import share


def read(win):
    return share(win, "winograd_tile_matmul")
