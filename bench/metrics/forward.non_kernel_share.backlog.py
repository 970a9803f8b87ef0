"""Share of device-busy time spent outside the Pallas kernels: the float
first conv and its BatchNorm, the pack, pads, slices and the final
BatchNorm — the XLA ops around the kernels (device trace)."""

from bench.metrics import _shared


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    kernels = t.op_seconds(_shared.PALLAS)
    if kernels is None:
        return None
    busy = t.busy_s * t.chips       # op_seconds sums over chips
    return 100.0 * (busy - kernels) / busy
