"""Operations and bytes of Bi-Real Net-18, counted from its shapes.

The yardstick of the Bi-Real cell's roofline and utilization metrics,
as ``bench/counts.py`` is the CIFAR cells'. It counts the work the
network needs, whatever kernel does it: the binary multiply-accumulates
of the 16 block convs, and the float ones of the stem conv, the three
1x1 downsample convs and the FC head. One MAC is two operations.

Bytes of one block launch (the residual direct conv): its packed
filters, folded affine ``(a, b)`` and border classes ``c_pos`` once,
and per image the packed input map read, the f32 shortcut read, the f32
output written and the packed output written (one bit per binary
activation, four bytes per float one). Padding and VMEM scratch are
not the algorithm's and are not counted.

The shapes are the ``model`` block of the configuration file; nothing
comes from the program.
"""

from __future__ import annotations

from bench import counts

WORD_BITS = counts.WORD_BITS


def blocks(model: dict) -> list[tuple[int, int, int, int]]:
    """``(c_in, c_out, stride, hw_in)`` of every block."""
    side = model["image"][0]
    hw = ((side + 6 - 7) // 2 + 1 + 2 - 3) // 2 + 1   # stem /2, maxpool /2
    out, c = [], model["stem_width"]
    for s, (width, depth) in enumerate(zip(model["widths"], model["depths"])):
        for j in range(depth):
            stride = 2 if s > 0 and j == 0 else 1
            out.append((c, width, stride, hw))
            c, hw = width, (hw - 1) // stride + 1
    return out


def _hw_out(hw: int, stride: int) -> int:
    return (hw - 1) // stride + 1


def block_macs(model: dict) -> list[int]:
    """Binary MACs per image of each block conv (3x3, pad 1)."""
    k2 = model["kernel_size"] ** 2
    return [_hw_out(hw, s) ** 2 * k2 * cin * cout
            for cin, cout, s, hw in blocks(model)]


def binary_macs_per_image(model: dict) -> int:
    return sum(block_macs(model))


def float_macs_per_image(model: dict) -> int:
    """The stem conv (7x7/2 on the image), the 1x1 downsample convs and
    the FC head."""
    side = model["image"][0]
    stem_hw = (side + 6 - 7) // 2 + 1
    stem = stem_hw ** 2 * 49 * model["image"][2] * model["stem_width"]
    down = sum(_hw_out(hw, s) ** 2 * cin * cout
               for cin, cout, s, hw in blocks(model) if s == 2)
    return stem + down + model["widths"][-1] * model["classes"]


def forward_ops_per_image(model: dict) -> int:
    return 2 * (binary_macs_per_image(model) + float_macs_per_image(model))


def block_bytes(model: dict, block: int, images: int) -> int:
    """HBM bytes one block launch over ``images`` rows must move."""
    cin, cout, s, hw = blocks(model)[block]
    k2 = model["kernel_size"] ** 2
    weights = k2 * cin * cout // 8 + 2 * 4 * cout + 9 * 4 * cout
    out = _hw_out(hw, s) ** 2 * cout
    per_image = hw * hw * cin // 8 + 4 * out + 4 * out + out // 8
    return weights + images * per_image


def block_least_seconds(model: dict, block: int, images: int,
                        peaks: dict) -> float:
    """Least time of one block launch: the larger of its binary
    operations at the int8 peak and its bytes at the HBM bandwidth."""
    ops = 2 * images * block_macs(model)[block]
    return max(ops / peaks["int8_ops_s"],
               block_bytes(model, block, images) / peaks["hbm_bytes_s"])


def block_convs_least_seconds(model: dict, images: int, peaks: dict) -> float:
    """Least time of one forward's 16 block launches over ``images``."""
    return sum(block_least_seconds(model, b, images, peaks)
               for b in range(len(blocks(model))))
