"""The max-pool kernels' share of their roofline, as for the convs,
over the device time of the ops under the ``maxpool2d_pallas`` scope."""
from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "maxpool", "maxpool2d_pallas")
