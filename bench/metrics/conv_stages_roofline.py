"""Roofline share of the conv stages: the least time a chip could take
for the window's stage work, over the device time the stage launches
took (device trace).

The work is counted from shapes in ``bench/counts.py`` — binary MACs at
the int8 peak, packed maps and filters at the HBM bandwidth — for every
row a launch computed (pad rows included: the kernel computes them),
taken from the program's dispatch counters per extent class, so a later
kernel that does the same work another way reads against the same
yardstick. The launches are found by their name in the trace
(``_shared.CONV_STAGE``); a trace without them reads nothing.
"""

from bench import counts
from bench.metrics import _shared


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    took = t.op_seconds(_shared.CONV_STAGE)
    if not took:
        return None
    stages = range(len(counts.conv_stages(ctx.model)))
    least = 0.0
    for extent, n in ctx.counters["per_extent"].items():
        rows = int(extent) // ctx.chips
        per_launch = sum(counts.stage_least_seconds(ctx.model, s, rows,
                                                    ctx.peaks)
                         for s in stages)
        least += n * ctx.chips * per_launch
    return 100.0 * least / took
