"""Whole-forward utilization: images per second over the window times
the operations of one image (``bench/counts.py``), over the cell's chips
at the int8 peak (+-1 operands are exact in int8)."""

from bench import counts


def read(ctx):
    rate = ctx.e2e.get("images_s")
    if not rate:
        return None
    ops = rate * counts.forward_ops_per_image(ctx.model)
    return 100.0 * ops / (ctx.chips * ctx.peaks["int8_ops_s"])
