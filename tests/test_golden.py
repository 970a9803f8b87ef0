"""Golden-logits regression: the PACKED CIFAR-BNN logits of the
committed TRAINED checkpoint are pinned in tests/golden/bnn_logits.json
(float32 hex — exact), so a kernel refactor that silently changes
numerics fails tier-1 immediately instead of shipping.

Since the train-to-serve loop closed, the fixture is generated from
tests/golden/bnn_trained_ckpt.npz — a sign-form checkpoint
(core.bnn.save_binary_checkpoint) produced by a real STE training run
(examples/bnn_cifar.py). Regressing the logits a TRAINED model serves
is the point: a random init exercises the same kernels but not the
same stakes.

The input images come from NumPy's generator
(``np.random.default_rng(image_seed)``), never the jax PRNG, whose
defaults change between jax releases.

The fixture is EXACT by design. Two legitimate reasons it can move:

* an intentional numerics change — regenerate with
  ``PYTHONPATH=src python scripts/gen_golden_logits.py`` and commit the
  diff (reviewers see exactly which logits moved);
* a jax/XLA upgrade that re-associates the float first-conv / final-BN
  math — the same ulp-level caveat as
  ``test_bnn_fused_matches_packed_with_trained_stats``. If only a
  handful of entries drift by <= 1e-4 right after a jax bump, that is
  toolchain noise, not a kernel bug: regenerate and note the version.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.binarize import QuantMode
from repro.core.bnn import (
    BINARY_CKPT_FORMAT,
    BNNConfig,
    bnn_apply,
    bnn_apply_fused,
    bnn_eval_logits,
    load_binary_checkpoint,
    pack_bnn_params,
    pack_bnn_params_fused,
)

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
FIXTURE = GOLDEN_DIR / "bnn_logits.json"


@pytest.fixture(scope="module")
def golden():
    data = json.loads(FIXTURE.read_text())
    logits = np.array(
        [[float.fromhex(v) for v in row] for row in data["logits_hex"]],
        np.float32,
    )
    assert list(logits.shape) == data["shape"]
    return data, logits


@pytest.fixture(scope="module")
def seeded(golden):
    data, _ = golden
    assert "checkpoint" in data, (
        "fixture must be generated from the trained checkpoint "
        "(scripts/gen_golden_logits.py without --random-init)"
    )
    ckpt = FIXTURE.parent.parent.parent / data["checkpoint"]
    params = load_binary_checkpoint(ckpt)
    rng = np.random.default_rng(data["image_seed"])
    images = rng.standard_normal(
        tuple(data["shape"][:1]) + (32, 32, 3)
    ).astype(np.float32)
    return params, jnp.asarray(images)


def test_checkpoint_format_tag():
    with np.load(GOLDEN_DIR / "bnn_trained_ckpt.npz") as z:
        assert str(z["format"]) == BINARY_CKPT_FORMAT


def test_checkpoint_latents_are_sign_form(seeded):
    """The committed checkpoint stores 1 bit/weight; loading must
    reconstruct exact ±1.0 latents (sign(sign(w)) == sign(w) is what
    makes the forward bit-identical to the float run that produced
    it)."""
    params, _ = seeded
    for group in ("conv", "fc"):
        for layer in params[group]:
            w = np.asarray(layer["w"])
            assert set(np.unique(w)) <= {-1.0, 1.0}


def test_packed_logits_match_golden(golden, seeded):
    _, want = golden
    params, images = seeded
    got = bnn_apply(
        pack_bnn_params(params), images,
        BNNConfig(mode=QuantMode.PACKED, engine="xla"),
    )
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)


def test_float_boundary_matches_golden(golden, seeded):
    """The FAKE_QUANT eval forward — the reference the training loop
    optimizes — pins to the SAME fixture as the packed engines: this is
    the train-to-serve contract (DESIGN.md §12) grounded in a committed
    artifact."""
    _, want = golden
    params, images = seeded
    got = bnn_eval_logits(params, images)
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)


def test_fused_pipeline_matches_golden(golden, seeded):
    """The fused packed pipeline is pinned to the SAME fixture — the
    bit-identity chain (fused == unfused PACKED) grounds out in one
    committed artifact rather than only in relative tests."""
    _, want = golden
    params, images = seeded
    got = bnn_apply_fused(pack_bnn_params_fused(params), images,
                          engine="xla")
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the conftest's 8 forced host devices")
def test_golden_invariant_to_device_count(golden, seeded):
    """ISSUE 7: serving is invariant to the mesh size — the whole
    session already runs under 8 forced host devices (conftest), and
    the jitted single-device forward, the mesh-sharded dispatch at
    every mesh size that divides the fixture batch (2 and 4), and the
    8-device mesh through the ragged executor's bit-neutral
    pad-and-slice path (4 real rows padded to extent 8) must all agree
    BIT-IDENTICALLY with each other.

    Against the (eager-computed) fixture the jitted paths are pinned to
    <= 1 ulp instead: with a TRAINED checkpoint the final BN affine has
    b != 0, and XLA's jit-time FMA contraction of ``a*dot + b`` rounds
    once where the eager path rounds twice. Deterministic per build —
    the old random-init fixture masked it only because its folded
    b == 0 makes the FMA exact."""
    from repro.core.bnn import bnn_serve_fn
    from repro.launch.mesh import make_serving_mesh
    from repro.serve import RaggedExecutorCache

    _, want = golden
    params, images = seeded
    fused = pack_bnn_params_fused(params)
    base = np.asarray(bnn_serve_fn(engine="xla")(fused, images),
                      np.float32)
    np.testing.assert_allclose(base, want, rtol=0, atol=2.4e-7)
    for n_dev in (2, 4):  # divide the 4-row fixture batch exactly
        fn = bnn_serve_fn(engine="xla", mesh=make_serving_mesh(n_dev))
        got = np.asarray(fn(fused, images), np.float32)
        np.testing.assert_array_equal(got, base)
    cache = RaggedExecutorCache(fused, engine="xla",
                                mesh=make_serving_mesh(8))
    got = np.asarray(cache.run(np.asarray(images)), np.float32)
    np.testing.assert_array_equal(got, base)


def test_golden_fixture_is_exact_hex(golden):
    """Guard the fixture format itself: hex floats must round-trip and
    carry the ±1-dot structure (integer-valued dots scaled by the BN
    affine make most entries near-integers — a wholesale format break
    shows up as NaNs/garbage here)."""
    data, logits = golden
    assert np.isfinite(logits).all()
    rt = [[float.fromhex(float(v).hex()) for v in row] for row in logits]
    np.testing.assert_array_equal(np.asarray(rt, np.float32), logits)
