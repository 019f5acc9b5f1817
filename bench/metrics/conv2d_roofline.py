"""The conv kernels' share of their roofline: for every conv layer of
every call in the traced window, the least time its logical operations
and bytes need on this chip (``bench/counts.py``), summed, over the
device time of the ops under the ``conv2d_pallas`` scope (the padding
it does included)."""
from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "conv", "conv2d_pallas")
