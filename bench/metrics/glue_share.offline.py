"""Share of the device's op time spent outside compiled kernels: the
XLA glue between them (unfused adds, pads, relayout copies, pools, the
global average), the mean over the cell's chips (offline cells)."""


def read(ctx):
    if ctx.trace is None:
        return None
    shares = []
    for ops in ctx.trace.devices.values():
        total = sum(o.end - o.start for o in ops)
        if total:
            glue = sum(o.end - o.start for o in ops if not o.kernel)
            shares.append(100.0 * glue / total)
    return sum(shares) / len(shares) if shares else None
