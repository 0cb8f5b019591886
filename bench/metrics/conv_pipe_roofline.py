"""conv_pipe's share of its roofline: the least time of the conv layers
it ran (from the configuration's published shapes) over the device time
of its kernel events."""
from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, ("fused_conv",), "conv")
