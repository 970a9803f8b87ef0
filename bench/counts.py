"""Operations and bytes of the BNN, counted from its shapes.

The yardstick for every roofline and utilization metric of the
benchmark. It counts the work the network needs, not the work a
particular kernel happens to do: binary multiply-accumulates of the
interior convolutions and the fully connected layers, and the float
multiply-accumulates of the first convolution. One MAC is two
operations. Bytes of a conv stage launch are its packed filters and
affines plus the packed input and pooled output maps (one bit per
activation); the padded border and any scratch the kernel keeps in
VMEM are not the algorithm's and are not counted.

The shapes are the ``model`` block of the cell's configuration file
(``bench/configs/<config>.json``), which the plain reference
(``bench/reference.py``) reads too; nothing comes from the program, so
no change to the program can move the yardstick.
"""

from __future__ import annotations

import json
import pathlib

WORD_BITS = 32

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def conv_channels(model: dict) -> list[tuple[int, int]]:
    """``(c_in, c_out)`` of each 3x3 convolution."""
    return [tuple(c) for c in model["conv_channels"]]


def conv_stages(model: dict) -> list[tuple[int, ...]]:
    """The interior (binary) convs grouped into pool-terminated stages,
    as a conv-stage kernel launches them."""
    stages, cur = [], []
    for i in range(1, len(model["conv_channels"])):
        cur.append(i)
        if i in model["pool_after"]:
            stages.append(tuple(cur))
            cur = []
    if cur:
        stages.append(tuple(cur))
    return stages


def _conv_out_hw(model: dict) -> list[int]:
    """Spatial size each conv computes at (a 2x2 pool halves it after)."""
    hw, out = model["image"][0], []
    for i in range(len(model["conv_channels"])):
        out.append(hw)
        if i in model["pool_after"]:
            hw //= 2
    return out


def conv_macs(model: dict) -> list[int]:
    """Multiply-accumulates per image of each conv (stride 1, same)."""
    k2 = model["kernel_size"] ** 2
    return [hw * hw * k2 * cin * cout
            for hw, (cin, cout) in zip(_conv_out_hw(model),
                                       conv_channels(model))]


def fc_macs(model: dict) -> list[int]:
    return [fin * fout for fin, fout in model["fc_sizes"]]


def binary_macs_per_image(model: dict) -> int:
    """Binary MACs of one image: every conv but the first, every FC."""
    return sum(conv_macs(model)[1:]) + sum(fc_macs(model))


def float_macs_per_image(model: dict) -> int:
    """Float MACs of one image: the first conv on real-valued pixels."""
    return conv_macs(model)[0]


def forward_ops_per_image(model: dict) -> int:
    """Operations of one forward pass (2 per MAC, binary and float)."""
    return 2 * (binary_macs_per_image(model) + float_macs_per_image(model))


def stage_ops(model: dict, stage: int, images: int) -> int:
    """Operations of one conv stage launch over ``images`` rows."""
    macs = conv_macs(model)
    return 2 * images * sum(macs[i] for i in conv_stages(model)[stage])


def stage_bytes(model: dict, stage: int, images: int) -> int:
    """HBM bytes one conv stage launch over ``images`` rows must move:
    packed filters and the (a, b) f32 affines once, the packed input map
    read and the pooled packed output map written per image."""
    convs = conv_stages(model)[stage]
    ch, hw, k2 = conv_channels(model), _conv_out_hw(model), model["kernel_size"] ** 2
    weights = sum(ch[i][1] * k2 * ch[i][0] // WORD_BITS * 4 + 2 * 4 * ch[i][1]
                  for i in convs)
    first, last = convs[0], convs[-1]
    in_map = hw[first] ** 2 * ch[first][0] // WORD_BITS * 4
    out_hw = hw[last] // 2 if last in model["pool_after"] else hw[last]
    out_map = out_hw ** 2 * ch[last][1] // WORD_BITS * 4
    return weights + images * (in_map + out_map)


def stage_least_seconds(model: dict, stage: int, images: int,
                        peaks: dict) -> float:
    """Least time a chip could take for one stage launch: the larger of
    its operations at the int8 peak (+-1 operands are exact in int8)
    and its bytes at the HBM bandwidth."""
    return max(stage_ops(model, stage, images) / peaks["int8_ops_s"],
               stage_bytes(model, stage, images) / peaks["hbm_bytes_s"])


def peaks_for(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
