"""Share of dispatched rows that were padding, over the window.

Source: the program's ``ServeStats`` counters (padded and real rows of
every dispatch), as deltas over the measured window. Pad rows are
extent-class padding of a ragged batch: device work that serves no
request.
"""


def read(ctx):
    c = ctx.counters
    rows = c["real_rows"] + c["padded_rows"]
    if not rows:
        return None
    return 100.0 * c["padded_rows"] / rows
