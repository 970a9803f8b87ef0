"""Share of device-busy time spent outside the Pallas kernels in the
Bi-Real cell: the input normalisation, the stem conv, its BN and the
maxpool, the first pack, the downsample shortcuts, the head — the XLA
ops around the block convs (device trace)."""

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
