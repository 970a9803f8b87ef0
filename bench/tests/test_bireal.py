"""The Bi-Real cell's yardstick, image pool and check on the CPU: the
counts of the published network, a seeded uint8 pool, and a whole run at
a reduced size (32x32 images, widths 32/64/96/128, the pure-XLA engine)
that reads ``correct``; each fault planted under the timed path makes
it read not correct, and so does the bfloat16 control."""

import json
import pathlib
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts_bireal, reference_bireal, run
from bench.drivers import serve_bireal

DATA = pathlib.Path(__file__).resolve().parent / "data"
MODEL = json.loads((DATA.parents[1] / "configs"
                    / "bireal18_imagenet_serve.json").read_text())["model"]
TINY = json.loads((DATA / "tiny_bireal.json").read_text())


def test_counts_of_the_published_network():
    assert counts_bireal.binary_macs_per_image(MODEL) == 1_676_279_808
    assert counts_bireal.float_macs_per_image(MODEL) == 137_793_536
    assert counts_bireal.forward_ops_per_image(MODEL) == 3_628_146_688
    blocks = counts_bireal.blocks(MODEL)
    assert len(blocks) == 16
    assert [b[3] for b in blocks[::4]] == [56, 56, 28, 14]
    assert [b[2] for b in blocks].count(2) == 3


def test_block_bytes_and_least_time():
    peaks = {"int8_ops_s": 393e12, "hbm_bytes_s": 819e9}
    # a layer-1 block: 56x56x64 packed in and out, f32 shortcut in and
    # output out, 64x576 packed filters, (a, b) and c_pos
    per_image = 56 * 56 * 64 // 8 * 2 + 56 * 56 * 64 * 4 * 2
    weights = 9 * 64 * 64 // 8 + 8 * 64 + 36 * 64
    assert counts_bireal.block_bytes(MODEL, 0, 64) == weights + 64 * per_image
    # bound by bytes, not by operations
    assert counts_bireal.block_least_seconds(MODEL, 0, 1, peaks) == (
        pytest.approx((weights + per_image) / 819e9))
    assert 2 * counts_bireal.block_macs(MODEL)[0] / 393e12 < per_image / 819e9


def test_image_pool_is_seeded():
    mix = {"image_pool": 4}
    a = serve_bireal.image_pool(mix, 2**31 + 7, 16)
    b = serve_bireal.image_pool(mix, 2**31 + 7, 16)
    c = serve_bireal.image_pool(mix, 2**31 + 8, 16)
    assert a.dtype == np.uint8 and a.shape == (4, 16, 16, 3)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() < 16 and a.max() > 240


def _run():
    cell = run.Cell(workload="bireal18_backlog", config=TINY,
                    mix=json.loads((DATA / "tiny_backlog_bireal.json")
                                   .read_text()),
                    chips=1, seed=2**31 + 3, seconds=1.0, trace=False,
                    t_start=time.perf_counter())
    return run.run_cell(run.load_spec(), cell)


def test_sound_run_is_correct():
    line = _run()
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "images_s"}
    assert line["checks"]["logit_gap"]["value"] <= 1e-5
    assert line["checks"]["unserved"]["value"] == 0


def _no_shortcut(p, x):
    n, h, w, _ = x.shape
    return jnp.zeros((n, h // 2, w // 2, p["w"].shape[1]), x.dtype)


def _no_border_correction(w_pm1, h, w, *, stride, pad):
    return jnp.zeros((3, w_pm1.shape[0], 3), jnp.int32)


@pytest.mark.parametrize("target,fault", [
    ("repro.core.bireal._downsample", _no_shortcut),
    ("repro.core.bitops.border_correction", _no_border_correction),
], ids=["shortcut_dropped", "one_padded_without_c_pos"])
def test_fault_under_the_timed_path_reads_incorrect(monkeypatch, target,
                                                     fault):
    monkeypatch.setattr(target, fault)
    line = _run()
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > TINY["check"]["logit_gap"]


def test_bfloat16_control_reads_incorrect():
    net = reference_bireal.Net.of(TINY["model"])
    params = reference_bireal.make_params(2**31 + 5, net)
    images = serve_bireal.image_pool({"image_pool": 8}, 5, net.image)
    want = reference_bireal.logits(params, net, images)
    ctrl = reference_bireal.logits(params, net, images, dtype=jnp.bfloat16)
    folded = reference_bireal.logits(params, net, images, fold_bn=True)
    limit = TINY["check"]["logit_gap"]
    assert reference_bireal._gap(folded, want).mean() < limit / 100
    assert reference_bireal._gap(ctrl, want).mean() > limit
