"""Bi-Real Net-18's serving path at a reduced size (32x32 images, stage
widths 32/64/96/128, two blocks in the first stage and one in each of
the others, 10 classes): the same block structure as the published
network — identity and downsample shortcuts, stride-2 binary convs,
maps down to 2x2 and 1x1.

* the residual direct conv (Pallas, interpret mode here) matches its
  XLA oracle bit for bit at stride 1 and 2;
* ``c_pos`` turns the one-padded popcount dot into the zero-padded dot
  exactly, on a 7x7 map and on maps where one output touches both
  borders;
* the served logits agree with the plain float32 reference on seeded
  weights within the benchmark configuration's limit (the ``xnor`` and
  ``xla`` engines' bit identity, also on a mesh, is in
  ``tests/test_sharded_serve.py``);
* ``ContinuousServingEngine`` serves uint8 requests of the new shape,
  rejects others, and fails over ``xnor -> xla`` logit-exactly.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import bireal, bitops
from repro.kernels import ops
from repro.models import bireal_reference
from repro.serve import (ContinuousServingEngine, FallbackPolicy, FaultPlan,
                         FaultSpec)

ARCH = bireal.BiRealArch(image=32, stem_width=32, widths=(32, 64, 96, 128),
                         depths=(2, 1, 1, 1), classes=10)
LIMIT = json.loads((pathlib.Path(__file__).resolve().parents[1] / "bench"
                    / "configs" / "bireal18_imagenet_serve.json")
                   .read_text())["check"]["logit_gap"]


@pytest.fixture(scope="module")
def params():
    return jax.jit(bireal.init_bireal_params, static_argnums=1)(
        jax.random.PRNGKey(15), ARCH)


@pytest.fixture(scope="module")
def packed(params):
    return jax.jit(bireal.pack_bireal_params, static_argnums=1)(params, ARCH)


@pytest.fixture(scope="module")
def images():
    rng = np.random.default_rng(15)
    return rng.integers(0, 256, size=(4, 32, 32, 3), dtype=np.uint8)


def _layer(hw, c, d, stride, seed=0):
    """A block conv's operands: packed map, packed filters, +-1 filters,
    affine, c_pos and shortcut."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(2, hw, hw, c)).astype(np.float32)
    w = np.where(rng.normal(size=(d, 3, 3, c)) >= 0, 1.0, -1.0)
    w = jnp.asarray(w, jnp.float32)
    oh = (hw - 1) // stride + 1
    return dict(
        x=x, w=w, xp=bitops.pack_channels(jnp.asarray(x)),
        wp=bitops.pack_bits(w.reshape(d, -1), axis=-1),
        a=jnp.asarray(rng.uniform(0.01, 0.1, d), jnp.float32),
        b=jnp.asarray(rng.normal(size=d), jnp.float32),
        cpos=bitops.border_correction(w, hw, hw, stride=stride, pad=1),
        r=jnp.asarray(rng.normal(size=(2, oh, oh, d)), jnp.float32))


@pytest.mark.parametrize("hw,c,d,stride,block_d", [
    (7, 64, 64, 1, "auto"),
    (8, 32, 64, 2, "auto"),
    (4, 64, 96, 2, 64),       # D padded to two 64-channel tiles
], ids=["7x7_stride1", "8x8_stride2", "ragged_d_stride2"])
def test_residual_kernel_matches_oracle(hw, c, d, stride, block_d):
    L = _layer(hw, c, d, stride)
    args = (L["wp"], L["xp"], 9 * c, L["a"], L["b"], L["cpos"], L["r"])
    y0, p0 = bitops.residual_conv_oracle(*args, kh=3, kw=3, stride=stride,
                                         pad=1)
    y1, p1 = ops.bireal_block_conv(*args, stride=stride, block_d=block_d,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p0))
    assert p1.shape[-1] == -(-d // 32)


@pytest.mark.parametrize("hw,stride", [(7, 1), (8, 2), (2, 1), (2, 2),
                                       (1, 1)])
def test_border_correction_gives_the_zero_padded_dot(hw, stride):
    L = _layer(hw, 32, 32, stride, seed=hw)
    sx = jnp.where(jnp.asarray(L["x"]) >= 0, 1.0, -1.0)
    want = lax.conv_general_dilated(
        sx, jnp.transpose(L["w"], (1, 2, 3, 0)), (stride, stride),
        ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    one_padded = bitops.direct_conv_dot(L["wp"], L["xp"], 9 * 32, kh=3,
                                        kw=3, stride=stride, pad=1)
    oh = one_padded.shape[1]
    got = one_padded - bitops._border_map(L["cpos"], oh, oh)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).astype(np.int32))
    if hw == 7:   # 24 of the 49 positions are border positions
        assert int((np.asarray(one_padded) != np.asarray(got))
                   .any(axis=(0, 3)).sum()) == 24


def test_border_taps_refuses_a_padded_interior():
    assert bitops.border_taps(7, 3, 1, 1) == [
        frozenset({0}), frozenset(), frozenset({2})]
    assert bitops.border_taps(1, 3, 1, 1)[0] == frozenset({0, 2})
    with pytest.raises(ValueError):
        bitops.border_taps(9, 5, 1, 3)


def test_served_logits_agree_with_the_plain_reference(params, packed,
                                                      images):
    """The ``xla`` engine here; the ``xnor`` engine's logits equal its
    bit for bit (``tests/test_sharded_serve.py``)."""
    want = np.asarray(bireal_reference.forward(params, jnp.asarray(images)))
    got = np.asarray(bireal.bireal_serve_fn(engine="xla", arch=ARCH)(
        packed, jnp.asarray(images)))
    gap = (np.abs(got - want).max(-1)
           / np.maximum(1.0, np.abs(want).max(-1)))
    assert gap.max() <= LIMIT / 100
    assert np.abs(want).max() > 1.0    # not a degenerate network


def test_alpha_is_folded_into_the_affine(params, packed):
    p, q = params["blocks"][0], packed["blocks"][0]
    bn = p["bn"]
    s = bn["gamma"] / jnp.sqrt(bn["var"] + ARCH.bn_eps)
    alpha = jnp.mean(jnp.abs(p["w"]), axis=(1, 2, 3))
    np.testing.assert_allclose(q["a"], s * alpha, rtol=1e-6)
    np.testing.assert_allclose(q["b"], bn["beta"] - s * bn["mean"],
                               rtol=1e-5, atol=1e-6)


def _engine(packed, **kw):
    return ContinuousServingEngine(
        packed, max_rows=4, max_wait_s=0.0,
        model=bireal.served_model(ARCH, name="bireal_tiny"), **kw)


def _serve(engine, requests):
    rids = [engine.submit(r) for r in requests]
    engine.drain()
    return [engine.take(rid) for rid in rids]


def test_continuous_engine_serves_uint8_requests(packed, images):
    eng = _engine(packed, engine="xla")
    out = _serve(eng, [images[:3], images[3:]])
    want = np.asarray(bireal.bireal_serve_fn(engine="xla", arch=ARCH)(
        packed, jnp.asarray(images)))
    np.testing.assert_array_equal(np.concatenate(out), want)
    assert eng.executors.key(4)[-1] == "bireal_tiny"
    for bad in (images[:, :16], images[..., :2],
                images.astype(np.float32)):
        with pytest.raises(ValueError):
            eng.submit(bad)


def test_failover_demotes_xnor_to_xla_logit_exactly(packed, images):
    eng = _engine(packed, engine="xnor",
                  fallback=FallbackPolicy(fused_params=packed,
                                          failures_before_demote=1,
                                          warm=False),
                  faults=FaultPlan([FaultSpec("raise", at=0, count=1,
                                              engine="xnor")]))
    out = _serve(eng, [images])
    assert eng.executors.engine == "xla"
    want = np.asarray(bireal.bireal_serve_fn(engine="xla", arch=ARCH)(
        packed, jnp.asarray(images)))
    np.testing.assert_array_equal(out[0], want)
