"""The Bi-Real check's control and its float32 reading: the plain
reference computed with the residual stream and the float convs in
bfloat16, put in the program's place and read by the same comparison as
a run (a sound comparison reads it as not correct); and the reference
with every BatchNorm folded into a scale and shift, the other float32
ordering, whose gap sets the limit.

    python3 bench/control_bireal.py --workload bireal18_backlog --seeds 1,2,3

For each seed it makes the cell's weights and image pool as a run does,
takes as many images as a run's check compares, and prints both gaps
(the mean over the images, as a run's check takes it, and the summary
beside it) next to the cell's limit.
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
    import jax.numpy as jnp
    import numpy as np

    from bench import reference_bireal, run, traffic
    from bench.drivers.serve_bireal import image_pool

    spec = run.load_spec()
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = run.make_cell(spec, args.workload, seed, 1.0, False, 0.0)
        mix = cell.mix
        net = reference_bireal.Net.of(cell.config["model"])
        params = reference_bireal.make_params(seed, net)
        # a run's sample: check_requests requests and the largest one
        n = int(round(mix["check_requests"] * traffic.mean_size(mix))
                + mix["sizes"]["max"])
        images = image_pool(mix, seed, net.image)[:n]
        want = reference_bireal.logits(params, net, images)
        gap = {
            name: reference_bireal._gap(
                reference_bireal.logits(params, net, images, **kw), want)
            for name, kw in (("control_logit_gap", {"dtype": jnp.bfloat16}),
                             ("folded_f32_logit_gap", {"fold_bn": True}))}
        print(json.dumps({
            "workload": args.workload, "seed": seed, "images": n,
            **{k: float(v.mean()) for k, v in gap.items()},
            **{f"{k}_summary": reference_bireal.gap_summary(v)
               for k, v in gap.items()},
            "median_control_gap": float(np.median(gap["control_logit_gap"])),
            "limit": cell.config["check"]["logit_gap"]}), flush=True)


if __name__ == "__main__":
    main()
