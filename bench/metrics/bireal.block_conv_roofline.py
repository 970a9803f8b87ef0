"""Roofline share of the Bi-Real block convs: the least time a chip
could take for the window's block-conv work, over the device time of the
launches named ``bireal_block_conv`` (device trace).

Least time is, per launch, the larger of its binary operations at the
int8 peak and its bytes (packed map in, packed filters, f32 shortcut
in, f32 output out, packed output out) at the HBM bandwidth
(``bench/counts_bireal.py``), for every row a launch computed, from the
program's dispatch counters per extent class: a later kernel that does
the same work is read against the same yardstick. A trace without such
launches reads nothing.
"""

from bench import counts_bireal

BLOCK_CONV = r"bireal_block_conv"


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    took = t.op_seconds(BLOCK_CONV)
    if not took:
        return None
    least = 0.0
    for extent, n in ctx.counters["per_extent"].items():
        rows = int(extent) // ctx.chips
        least += n * ctx.chips * counts_bireal.block_convs_least_seconds(
            ctx.model, rows, ctx.peaks)
    return 100.0 * least / took
