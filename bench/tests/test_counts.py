"""The yardstick: operations and bytes counted from the BNN's shapes, and
the table of peaks."""

import json
import pathlib

import pytest

from bench import counts

M = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                / "bnn_cifar10_serve.json").read_text())["model"]


def test_binary_and_float_macs_per_image():
    # convs 1-5: 604.0 M; fc0-2: 9.45 M; first conv (float): 3.54 M
    assert sum(counts.conv_macs(M)[1:]) == 603_979_776
    assert sum(counts.fc_macs(M)) == 9_447_424
    assert counts.binary_macs_per_image(M) == 613_427_200
    assert counts.float_macs_per_image(M) == 3_538_944
    assert counts.forward_ops_per_image(M) == 2 * (613_427_200 + 3_538_944)


@pytest.mark.parametrize("stage,ops_per_image,weights,in_map,out_map", [
    # conv1 (+pool): 32x32 map of 128 ch in, 16x16 of 128 out
    (0, 2 * 150_994_944, 128 * 36 * 4 + 8 * 128, 32 * 32 * 4 * 4, 16 * 16 * 4 * 4),
    # conv2 + conv3 (+pool): 16x16x128 in, 8x8x256 out
    (1, 2 * (75_497_472 + 150_994_944),
     256 * 36 * 4 + 8 * 256 + 256 * 72 * 4 + 8 * 256,
     16 * 16 * 4 * 4, 8 * 8 * 8 * 4),
    # conv4 + conv5 (+pool): 8x8x256 in, 4x4x512 out
    (2, 2 * (75_497_472 + 150_994_944),
     512 * 72 * 4 + 8 * 512 + 512 * 144 * 4 + 8 * 512,
     8 * 8 * 8 * 4, 4 * 4 * 16 * 4),
])
def test_stage_ops_and_bytes(stage, ops_per_image, weights, in_map, out_map):
    assert counts.stage_ops(M, stage, 1) == ops_per_image
    assert counts.stage_ops(M, stage, 32) == 32 * ops_per_image
    assert counts.stage_bytes(M, stage, 0) == weights
    assert counts.stage_bytes(M, stage, 32) == weights + 32 * (in_map + out_map)


def test_stages_cover_the_interior_convs():
    assert counts.conv_stages(M) == [(1,), (2, 3), (4, 5)]
    total = sum(counts.stage_ops(M, s, 1) for s in range(3))
    assert total == 2 * sum(counts.conv_macs(M)[1:])


def test_peaks_by_device_kind():
    p = counts.peaks_for("TPU v5 lite")
    assert p["bf16_flops_s"] == 197e12
    assert p["int8_ops_s"] == 393e12
    assert p["hbm_bytes_s"] == 819e9
    # a 32-row stage launch is bound by compute, not by bytes
    assert (counts.stage_ops(M, 2, 32) / p["int8_ops_s"]
            > counts.stage_bytes(M, 2, 32) / p["hbm_bytes_s"])
    assert counts.stage_least_seconds(M, 2, 32, p) == pytest.approx(
        counts.stage_ops(M, 2, 32) / 393e12)


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        counts.peaks_for(kind)
