"""The serving check's control: the plain reference computed in
bfloat16, put in the program's place, read by the same comparison as a
run. A sound comparison reads it as not correct.

    python3 bench/control.py --workload serve_backlog --seeds 1,2,3

For each seed it makes the cell's weights and image pool as a run does,
takes as many images as a run's check compares, and prints the widest
relative logit gap of the bfloat16 reference against the float32 one,
beside the cell's limit.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import reference, run, traffic

    spec = run.load_spec()
    bf16 = jax.jit(reference.forward, static_argnames=("net", "dtype"))
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = run.make_cell(spec, args.workload, seed, 1.0, False, 0.0)
        mix = cell.mix
        net = reference.Net.of(cell.config["model"])
        params = reference.make_params(seed, net)
        pool = traffic.image_pool(mix, seed)
        # a run's sample: check_requests requests and the largest one
        n = int(round(mix["check_requests"] * traffic.mean_size(mix))
                + mix["sizes"]["max"])
        images = pool[:n]
        ctrl = np.concatenate([
            np.asarray(bf16(params, jnp.asarray(images[i:i + 64]), net,
                            dtype=jnp.bfloat16))
            for i in range(0, n, 64)])
        gaps = reference.logit_gaps(params, net, images, ctrl)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "images": n, "control_logit_gap": float(gaps.max()),
                          "median_image_gap": float(np.median(gaps)),
                          "tied_channels": len(reference.tied_channels(
                              params, net)),
                          "limit": cell.config["check"]["logit_gap"]}),
              flush=True)


if __name__ == "__main__":
    main()
