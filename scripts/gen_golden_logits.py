"""(Re)generate the golden-logits fixture tests/golden/bnn_logits.json.

The fixture pins the PACKED CIFAR-BNN logits so kernel refactors that
silently change numerics fail tier-1 immediately (tests/test_golden.py).
Floats are stored as float32 hex strings — exact round-trip,
human-diffable.

Since the train-to-serve loop closed (DESIGN.md §12) the fixture is
generated from the committed TRAINED sign-form checkpoint
(tests/golden/bnn_trained_ckpt.npz, written by examples/bnn_cifar.py) —
the logits under regression are the ones a trained model actually
serves, not a random init's. ``--random-init SEED`` remains as a debug
escape hatch for bisecting numerics changes without a checkpoint.

Run from the repo root after an INTENTIONAL numerics change:

  PYTHONPATH=src python scripts/gen_golden_logits.py \
      --from-checkpoint tests/golden/bnn_trained_ckpt.npz
"""

from __future__ import annotations

import argparse
import json
import pathlib

import jax
import numpy as np

from repro.core.binarize import QuantMode
from repro.core.bnn import (
    BNNConfig,
    bnn_apply,
    init_bnn_params,
    load_binary_checkpoint,
    pack_bnn_params,
)

IMAGE_SEED = 2024
BATCH = 4
ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT = ROOT / "tests" / "golden" / "bnn_logits.json"
DEFAULT_CKPT = ROOT / "tests" / "golden" / "bnn_trained_ckpt.npz"


def golden_images(seed: int = IMAGE_SEED, batch: int = BATCH) -> np.ndarray:
    """The fixture's input images: standard normal float32 drawn with
    NumPy's PCG64 generator, so they depend on no jax PRNG default."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((batch, 32, 32, 3)).astype(np.float32)


def compute_logits(params) -> np.ndarray:
    images = golden_images()
    logits = bnn_apply(
        pack_bnn_params(params), images,
        BNNConfig(mode=QuantMode.PACKED, engine="xla"),
    )
    return np.asarray(logits, np.float32)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--from-checkpoint", type=pathlib.Path, default=DEFAULT_CKPT,
        help="sign-form checkpoint (core.bnn.save_binary_checkpoint) "
             "to pin logits for [default: the committed trained ckpt]",
    )
    ap.add_argument(
        "--random-init", type=int, default=None, metavar="SEED",
        help="debug escape hatch: pin a random init instead of a "
             "checkpoint (tests/test_golden.py only accepts the "
             "checkpoint form)",
    )
    args = ap.parse_args()

    if args.random_init is not None:
        params = init_bnn_params(jax.random.PRNGKey(args.random_init))
        source = {"param_seed": args.random_init}
        src_desc = f"init_bnn_params(PRNGKey({args.random_init}))"
    else:
        params = load_binary_checkpoint(args.from_checkpoint)
        rel = args.from_checkpoint.resolve().relative_to(ROOT)
        source = {"checkpoint": str(rel)}
        src_desc = f"trained sign-form checkpoint {rel}"

    logits = compute_logits(params)
    fixture = {
        "description": (
            "PACKED (engine=xla) logits of the CIFAR BNN for "
            f"{src_desc} on np.random.default_rng({IMAGE_SEED})"
            f".standard_normal(({BATCH}, 32, 32, 3)) as float32. "
            "float32 hex — exact. Regenerate ONLY for intentional "
            "numeric changes: scripts/gen_golden_logits.py"
        ),
        **source,
        "image_seed": IMAGE_SEED,
        "shape": list(logits.shape),
        "generated_with_jax": jax.__version__,
        "logits_hex": [[float(v).hex() for v in row] for row in logits],
    }
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(fixture, indent=2) + "\n")
    print(f"wrote {OUT}")
    print(logits)


if __name__ == "__main__":
    main()
