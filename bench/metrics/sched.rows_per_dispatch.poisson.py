"""Real (request) rows per dispatch over the window: how many images the
scheduler coalesces into one launch. Source: ``ServeStats`` counters."""


def read(ctx):
    c = ctx.counters
    if not c["dispatched"]:
        return None
    return c["real_rows"] / c["dispatched"]
