"""Whole-forward utilization of the Bi-Real cell: images per second over
the window times the operations of one image (``bench/counts_bireal.py``:
3,628,146,688 at 224x224), over the cell's chips at the int8 peak — the
yardstick of ``serve_mfu``."""

from bench import counts_bireal


def read(ctx):
    rate = ctx.e2e.get("images_s")
    if not rate:
        return None
    ops = rate * counts_bireal.forward_ops_per_image(ctx.model)
    return 100.0 * ops / (ctx.chips * ctx.peaks["int8_ops_s"])
