"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (device trace, ``bench/trace.py``)."""

from bench.metrics import _shared


def read(ctx):
    return _shared.idle_share(ctx)
