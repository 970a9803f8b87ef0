"""Serving benchmark: batched vs batch-1 throughput on the fused xnor
path, bucket/compile accounting, and the structural serving-traffic
model. Writes BENCH_serving.json at the repo root.

Full mode (default; several minutes — Pallas interpret compiles at
every bucket):

1. **Serving-config sweep** — ``tune_serving_blocks`` picks the ONE
   deployment-wide block config that maximizes throughput at the
   largest measured bucket (persisted in the PR-3 autotune cache).
2. **Per-bucket throughput** under that deployed config, on
   ``engine="xnor"`` (the Pallas fused kernels, interpret mode off-TPU
   — the literal fused xnor path). The headline ratio compares bucket
   >= 32 against batch-1 under the SAME deployed config: that is
   exactly the choice a serving fleet faces (one compiled config,
   dispatch now vs coalesce).
3. **Structural serving bytes** — per-dispatch HBM traffic splits into
   batch-invariant weight reads and per-image activation bytes;
   batching amortizes the former. Shape-derived, backend-independent.
4. **Engine traffic run** (xla engine, CPU-fast) — seeded ragged
   requests through the ServingEngine: bucket hit rates, padding
   overhead, flush reasons, and the steady-state compile invariant
   (compile count == buckets warmed, zero new compiles under traffic).
5. **Scheduler head-to-head** (interpret xnor path, both modes) — one
   deterministic open-loop arrival schedule driven through the bucket
   ladder AND the continuous scheduler (DESIGN.md §9), same engine,
   same traffic. Load and SLO self-calibrate to the machine: offered
   load targets ~60% of the top rung's measured capacity, the SLO is
   1.75x the top-rung service wall — the regime where coalesced rows
   land BETWEEN rungs, so the ladder pads to 32 while the continuous
   scheduler dispatches 16/24-row extents. Reports per-side open-loop
   p99 (latency from INTENDED arrival, not submit — the synchronous
   loop submits late while a dispatch blocks, and that wait is real),
   goodput (within-SLO images/s) and pad-row fraction. ``--check``
   exits nonzero unless the continuous side beats the ladder on BOTH
   p99 and goodput — the CI gate.

``--smoke`` (CI): skips the sweep, uses the xla fallback engine and a
tiny ladder for sections 1-4 and a shorter head-to-head window; still
writes the JSON with the same schema.

  PYTHONPATH=src python -m benchmarks.serving [--smoke] [--check]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.kernel_microbench import _ceil_div, fused_chain_traffic
from repro.core.bnn import (
    CONV_CHANNELS,
    FC_SIZES,
    POOL_AFTER,
    bnn_serve_fn,
    init_bnn_params,
    pack_bnn_params_fused,
)
from repro.kernels import autotune
from repro.serve import (
    ContinuousServingEngine,
    QueueFull,
    ServingEngine,
    percentile,
    tune_serving_blocks,
)
from repro.serve.executor import blocks_key

from benchmarks._util import bench_path, write_bench

BENCH_PATH = bench_path("serving")


# ---------------------------------------------------------------------------
# Structural serving-traffic model (shape-derived, backend-independent)
# ---------------------------------------------------------------------------

def serving_traffic_model(buckets=(1, 8, 32, 128)) -> dict:
    """Per-dispatch HBM bytes of the fused im2col chain at each bucket,
    split into batch-invariant weight bytes W and per-image activation
    bytes A: ``bytes(B) = W + B*A``. Serving at bucket B amortizes W
    over B images; the table reports the per-image amortization ratio
    ``(W + A) / (W/B + A)`` vs batch-1.
    """
    f32 = 4
    # -- W: every byte read once per dispatch regardless of batch.
    w_bytes = 0
    cin0, cout0 = CONV_CHANNELS[0]
    w_bytes += cout0 * 9 * cin0 * f32 + cout0 * f32      # float first conv
    w_bytes += 4 * cout0 * f32                            # its separate BN
    for cin, cout in CONV_CHANNELS[1:]:
        w_bytes += cout * _ceil_div(9 * cin, 32) * 4      # packed filters
        w_bytes += 2 * cout * f32                         # folded (a, b)
    for fin, fout in FC_SIZES[:-1]:
        w_bytes += fout * _ceil_div(fin, 32) * 4 + 2 * fout * f32
    fin_l, fout_l = FC_SIZES[-1]
    w_bytes += fout_l * _ceil_div(fin_l, 32) * 4 + fout_l * f32
    w_bytes += 4 * fout_l * f32                           # unfolded last BN

    # -- A: bytes that scale with every image in the dispatch.
    act = 32 * 32 * 3 * f32                               # input read
    act += 2 * 32 * 32 * cout0 * f32                      # float conv out w+r
    act += 2 * 32 * 32 * _ceil_div(cout0, 32) * 4         # first packed w+r
    # interior packed boundaries (write+read), per image:
    act += fused_chain_traffic(1)["total"]["fused_bytes"]
    # im2col packed patch matrices (write+read), per image:
    hw = 32
    for i, (cin, cout) in enumerate(CONV_CHANNELS):
        if i > 0:
            act += 2 * hw * hw * 9 * _ceil_div(cin, 32) * 4
        if i in POOL_AFTER:
            hw //= 2
    act += fout_l * f32                                   # logits write

    per_image_b1 = w_bytes + act
    rows = {
        int(b): {
            "dispatch_bytes": w_bytes + b * act,
            "per_image_bytes": w_bytes / b + act,
            "amortization_ratio_vs_batch1": per_image_b1 / (w_bytes / b + act),
        }
        for b in buckets
    }
    return {
        "weight_bytes": w_bytes,
        "act_bytes_per_image": act,
        "per_bucket": rows,
        "note": (
            "bytes(B) = W + B*A for the fused im2col chain; batching "
            "amortizes the batch-invariant weight reads W. Shape-derived "
            "— no wall clock involved."
        ),
    }


# ---------------------------------------------------------------------------
# Measured throughput
# ---------------------------------------------------------------------------

def measure_bucket_throughput(
    fused_params: dict,
    buckets,
    *,
    engine: str,
    blocks: object,
    key=None,
) -> dict:
    """Steady-state img/s per bucket under one (engine, blocks) config.

    One ``bnn_serve_fn`` serves every bucket (as in the executor cache:
    one jit fn, one executable per shape). Fewer repeats at larger
    buckets keep full-mode wall time bounded.
    """
    key = jax.random.PRNGKey(7) if key is None else key
    fn = bnn_serve_fn(engine=engine, blocks=blocks)
    out = {}
    for b in buckets:
        # interpret-mode timings on a small shared CPU are noisy;
        # spend repeats where a single run is cheapest
        reps = 6 if b == 1 else 3 if b <= 8 else 2 if b <= 32 else 1

        def call(b=b):
            x = jax.random.normal(jax.random.fold_in(key, b),
                                  (b, 32, 32, 3))
            return fn(fused_params, x)

        t = autotune.time_call(call, reps)
        out[int(b)] = {"wall_s": t, "img_per_s": b / t}
    return out


def traffic_run(fused_params: dict, *, seed: int = 0) -> dict:
    """Seeded ragged traffic through the ServingEngine (xla engine —
    CPU-fast; the batching/caching machinery is engine-independent).
    Returns the stats snapshot plus the steady-state compile check."""
    eng = ServingEngine(fused_params, engine="xla", buckets=(1, 4, 8),
                        max_wait_s=0.0)  # max_wait 0: dispatch every poll
    warmed = eng.warmup()
    compiles_after_warmup = eng.stats.executor_compiles
    rng = np.random.default_rng(seed)
    for _ in range(24):
        n = int(rng.integers(1, 9))
        eng.submit(rng.normal(size=(n, 32, 32, 3)).astype(np.float32))
        eng.step()
    eng.drain()
    snap = eng.snapshot()
    return {
        "snapshot": snap,
        "steady_state": {
            "buckets_warmed": warmed,
            "compiles_total": snap["executors"]["compiles"],
            "compiles_under_traffic": (
                snap["executors"]["compiles"] - compiles_after_warmup
            ),
            "compiles_equal_buckets_warmed": (
                snap["executors"]["compiles"] == warmed
            ),
        },
    }


# ---------------------------------------------------------------------------
# Scheduler head-to-head: bucket ladder vs continuous, same traffic
# ---------------------------------------------------------------------------

H2H_MAX_ROWS = 32        # continuous row budget == the ladder's top rung
H2H_BUCKETS = (1, 8, 32)
H2H_MAX_IMAGES = 8       # request sizes ~ U{1..8}, mean 4.5
H2H_UTILIZATION = 0.6    # offered load as a fraction of rung-32 capacity
H2H_SLO_FACTOR = 1.75    # SLO = factor * measured rung-32 service wall


def _arrival_schedule(seed: int, rate: float, duration_s: float,
                      max_images: int) -> list[tuple[float, int]]:
    """Deterministic open-loop schedule: ``(t_arrive, n_images)`` at a
    fixed inter-arrival interval with seeded sizes — both schedulers
    replay the IDENTICAL traffic."""
    rng = np.random.default_rng(seed)
    interval = 1.0 / rate
    out = []
    t = 0.0
    while t < duration_s:
        out.append((t, int(rng.integers(1, max_images + 1))))
        t += interval
    return out


def _drive_open_loop(eng, schedule, requests) -> dict:
    """Replay ``schedule`` through ``eng`` on the real clock.

    Latency is measured from each request's INTENDED arrival time, not
    its submit time: the synchronous dispatch loop submits late while a
    launch blocks, and for the ladder that blocked wait is exactly the
    tail this benchmark exists to expose — crediting it away would rig
    the comparison toward whichever side blocks longer.
    """
    lat = []
    rejected_images = 0
    t_intended: dict[int, float] = {}
    n_images: dict[int, int] = {}

    t0 = time.monotonic()
    i = 0
    while i < len(schedule):
        now = time.monotonic() - t0
        while i < len(schedule) and now >= schedule[i][0]:
            t_arr, _ = schedule[i]
            try:
                rid = eng.submit(requests[i])
                t_intended[rid] = t_arr
                n_images[rid] = requests[i].shape[0]
            except QueueFull:
                rejected_images += requests[i].shape[0]
            i += 1
        for rid in eng.step():
            eng.take(rid)
            lat.append(((time.monotonic() - t0) - t_intended.pop(rid),
                        n_images.pop(rid)))
        if i < len(schedule):
            time.sleep(min(0.001, max(0.0, schedule[i][0]
                                      - (time.monotonic() - t0))))
    for rid in eng.drain():
        eng.take(rid)
        lat.append(((time.monotonic() - t0) - t_intended.pop(rid),
                    n_images.pop(rid)))
    wall = time.monotonic() - t0
    return {"latencies": lat, "wall_s": wall,
            "rejected_images": rejected_images}


def _h2h_side(run: dict, snap: dict, slo_s: float) -> dict:
    lat = [l for l, _ in run["latencies"]]
    within = sum(n for l, n in run["latencies"] if l <= slo_s)
    served = sum(n for _, n in run["latencies"])
    bat = snap["batches"]
    return {
        "scheduler": snap["scheduler"],
        "requests_served": len(lat),
        "images_served": served,
        "images_rejected": run["rejected_images"],
        "open_loop_latency_s": {
            "p50": percentile(lat, 50),
            "p95": percentile(lat, 95),
            "p99": percentile(lat, 99),
            "max": max(lat) if lat else 0.0,
        },
        "goodput_img_per_s": within / run["wall_s"] if run["wall_s"] else 0.0,
        "images_within_slo": within,
        "pad_row_fraction": bat["pad_row_fraction"],
        "dispatch_shapes": bat["per_bucket"],
        "dispatched_rows": bat["dispatched_rows"],
        "real_rows": bat["real_rows"],
    }


def head_to_head(fused_params: dict, *, smoke: bool, seed: int = 11,
                 verbose: bool = True) -> dict:
    """Bucket ladder vs continuous scheduler on the interpret xnor path,
    identical deterministic open-loop traffic, self-calibrated load."""
    engine = "xnor"

    # Calibrate: one rung-32 forward (after a warmup execution) sets the
    # machine's service wall; load and SLO derive from it so the regime
    # — coalesced rows landing between rungs — survives machine-speed
    # differences (a fixed rate would under- or overload a faster or
    # slower container into a different operating point entirely).
    fn = bnn_serve_fn(engine=engine)
    x32 = jax.random.normal(jax.random.PRNGKey(seed), (H2H_MAX_ROWS, 32, 32, 3))
    fn(fused_params, x32).block_until_ready()
    t32 = autotune.time_call(
        lambda: fn(fused_params,
                   jax.random.normal(jax.random.PRNGKey(seed + 1),
                                     (H2H_MAX_ROWS, 32, 32, 3))), 1,
    )
    mean_imgs = (1 + H2H_MAX_IMAGES) / 2
    rate = H2H_UTILIZATION * (H2H_MAX_ROWS / t32) / mean_imgs
    slo_s = H2H_SLO_FACTOR * t32
    # Both sides get the SAME coalescing wait, scaled to the service
    # wall: with a near-zero wait each side fires tiny launches whose
    # fixed per-launch overhead swamps the scheduling signal; a
    # quarter-service wait lets arrivals coalesce into the regime the
    # comparison is about (rows between the 8 and 32 rungs).
    max_wait_s = 0.25 * t32
    # The window must be long enough for queue dynamics to surface:
    # pad-to-rung wastes ~the pad fraction of the ladder's compute, so
    # at this utilization the ladder runs at its capacity edge and its
    # queue (hence p99) grows across cycles, while the continuous side
    # holds steady — a short window would hide exactly that.
    duration_s = (12 if smoke else 20) * t32
    schedule = _arrival_schedule(seed, rate, duration_s, H2H_MAX_IMAGES)
    rng = np.random.default_rng(seed + 2)
    requests = [rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
                for _, n in schedule]
    if verbose:
        print(f"head-to-head: rung-32 wall {t32:.2f}s -> rate "
              f"{rate:.2f} req/s, SLO {slo_s:.2f}s, {len(schedule)} "
              f"requests over {duration_s:.0f}s per side")

    sides = {}
    for name in ("bucket", "continuous"):
        if name == "bucket":
            eng = ServingEngine(fused_params, engine=engine,
                                buckets=H2H_BUCKETS,
                                max_wait_s=max_wait_s)
            eng.stats.slo_s = slo_s
        else:
            eng = ContinuousServingEngine(
                fused_params, engine=engine, max_rows=H2H_MAX_ROWS,
                max_queue_rows=3 * H2H_MAX_ROWS, slo_s=slo_s,
                max_wait_s=max_wait_s,
            )
        eng.warmup()
        run = _drive_open_loop(eng, schedule, requests)
        sides[name] = _h2h_side(run, eng.snapshot(), slo_s)
        if verbose:
            s = sides[name]
            print(f"  {name:10s} p99 {s['open_loop_latency_s']['p99']:.2f}s"
                  f" | goodput {s['goodput_img_per_s']:.1f} img/s"
                  f" | pad rows {s['pad_row_fraction']:.1%}"
                  f" | shapes {s['dispatch_shapes']}")

    b, c = sides["bucket"], sides["continuous"]
    wins = {
        "p99": c["open_loop_latency_s"]["p99"] < b["open_loop_latency_s"]["p99"],
        "goodput": c["goodput_img_per_s"] > b["goodput_img_per_s"],
    }
    wins["both"] = wins["p99"] and wins["goodput"]
    if verbose:
        print(f"  continuous beats bucket: p99={wins['p99']} "
              f"goodput={wins['goodput']}")
    return {
        "engine": engine,
        "calibration": {"rung32_wall_s": t32, "rate_req_per_s": rate,
                        "slo_s": slo_s, "duration_s": duration_s,
                        "max_wait_s": max_wait_s,
                        "utilization_target": H2H_UTILIZATION,
                        "max_images": H2H_MAX_IMAGES},
        "bucket": b,
        "continuous": c,
        "continuous_beats_bucket": wins,
        "note": (
            "Identical deterministic open-loop traffic through both "
            "schedulers on the interpret xnor path. Latency is from "
            "intended arrival (open-loop convention). Load targets "
            f"{H2H_UTILIZATION:.0%} of rung-32 capacity so coalesced "
            "batches land between the 8 and 32 rungs: the ladder pads "
            "them to 32, the continuous scheduler dispatches tile-"
            "padded 16/24-row extents — the pad-row compute it removes "
            "is the p99/goodput margin."
        ),
    }


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run(smoke: bool = False, verbose: bool = True, write: bool = True) -> dict:
    params = init_bnn_params(jax.random.PRNGKey(0))
    fused = pack_bnn_params_fused(params)

    if smoke:
        engine, buckets, big = "xla", (1, 4, 8), 8
        blocks, sweep = "auto", None
        best_single_ratio = None
    else:
        engine, buckets, big = "xnor", (1, 8, 32, 128), 32
        timings: dict = {}
        blocks = tune_serving_blocks(fused, big, engine=engine,
                                     repeats=3, timings=timings)
        # Per-config batch-1 throughput: the batched-vs-batch1 ratio is
        # only meaningful with the config held FIXED across both sides,
        # and near-tied configs at the big bucket can differ 2x at
        # batch-1 — so record the whole (b1, b32, ratio) surface, not
        # just the winner's row.
        sweep = {}
        for c, t in timings.items():
            r1 = measure_bucket_throughput(fused, (1,), engine=engine,
                                           blocks=c)
            sweep[blocks_key(c)] = {
                "batch1_img_per_s": r1[1]["img_per_s"],
                "bucket32_img_per_s": big / t,
                "ratio_32_vs_1": (big / t) / r1[1]["img_per_s"],
            }
        best_single_ratio = max(r["ratio_32_vs_1"] for r in sweep.values())
        if verbose:
            print(f"serving-config sweep at bucket {big}:")
            for k, row in sweep.items():
                print(f"  {k:24s} b1 {row['batch1_img_per_s']:5.2f} "
                      f"b32 {row['bucket32_img_per_s']:6.2f} img/s "
                      f"({row['ratio_32_vs_1']:.2f}x)")
            print(f"  -> deployed config: {blocks_key(blocks)}")

    per_bucket = measure_bucket_throughput(
        fused, buckets, engine=engine, blocks=blocks
    )
    b1 = per_bucket[1]["img_per_s"]
    ratios = {
        b: row["img_per_s"] / b1 for b, row in per_bucket.items() if b != 1
    }
    # The system-level comparison this subsystem exists for: the serving
    # engine (bucketed + batched + serving-tuned blocks) vs the repo's
    # prior dispatch mode — one request at a time with per-shape "auto"
    # blocks and no batching. Both sides measured, same engine.
    naive_b1 = (sweep or {}).get("auto", {}).get("batch1_img_per_s", b1)
    batched_best = max(
        (row["img_per_s"] for b, row in per_bucket.items() if b >= 32),
        default=None,
    )
    engine_vs_naive = (
        batched_best / naive_b1 if batched_best is not None else None
    )
    structural = serving_traffic_model()
    traffic = traffic_run(fused)
    h2h = head_to_head(fused, smoke=smoke, verbose=verbose)

    result = {
        "mode": "smoke" if smoke else "full",
        "engine": engine,
        "deployed_blocks": blocks_key(blocks),
        "serving_config_sweep": sweep,
        "throughput": {
            "per_bucket": per_bucket,
            "batched_vs_batch1": ratios,
            "max_measured_bucket": max(buckets),
            # Three framings of "batched vs batch-1", most to least
            # favorable to batch-1 — all measured, none hidden:
            #   batched_vs_batch1      deployed config held fixed on
            #                          both sides (the fleet's marginal
            #                          choice: dispatch now vs coalesce)
            #   best_single_config...  best ratio any ONE config attains
            #                          (config fixed per row)
            #   engine_vs_naive_batch1 the serving engine at bucket>=32
            #                          vs the repo's PRIOR dispatch mode
            #                          (batch-1, per-shape auto blocks,
            #                          no batching) — what the subsystem
            #                          delivers end to end; note it
            #                          compounds batching with the
            #                          config change, so read it next
            #                          to the same-config rows.
            "best_single_config_ratio_32_vs_1": best_single_ratio,
            "engine_vs_naive_batch1": engine_vs_naive,
            # One verdict per framing (null in smoke mode, where the
            # xnor path and the >=32 buckets are not measured at all —
            # a False here would read as a failed criterion in every CI
            # artifact).
            "meets_3x_at_32": None if smoke else {
                "engine_vs_naive_batch1": bool(engine_vs_naive >= 3.0),
                "best_single_config": bool(best_single_ratio >= 3.0),
                "deployed_config": bool(
                    max((r for b, r in ratios.items() if b >= 32),
                        default=0.0) >= 3.0
                ),
            },
        },
        "structural_serving_bytes": structural,
        "engine_traffic": traffic,
        "head_to_head": h2h,
        "note": (
            "Throughput rows run the fused packed chain via bnn_serve_fn "
            "under ONE deployed block config (full mode: tuned for the "
            "largest-bucket steady state on the Pallas interpret xnor "
            "engine — the fused xnor path as it runs off-TPU; smoke: xla "
            "fallback). The batched-vs-batch1 ratio is the fleet's actual "
            "tradeoff: same compiled config, dispatch alone vs coalesce. "
            "CPU caveat: interpret-mode timings on this 2-core container "
            "are noisy (+-20%), and the per-image marginal cost bounds "
            "the measurable amortization at 1 + fixed/marginal (~3x "
            "here); larger buckets approach it. On accelerator backends "
            "the same fixed work (launch overhead, weight streaming, "
            "lane-padded FC tiles) is what the GPU batching wins of Khan "
            "et al. amortize. structural_serving_bytes is the backend-"
            "independent weight-amortization model; engine_traffic "
            "exercises the bucket ladder/cache on the CPU-fast xla "
            "engine."
        ),
    }
    if verbose:
        for b, row in per_bucket.items():
            extra = f"  ({ratios[b]:.2f}x vs batch-1)" if b != 1 else ""
            print(f"bucket {b:3d}: {row['img_per_s']:6.2f} img/s{extra}")
        if engine_vs_naive is not None:
            print(f"engine (bucket>=32, tuned) vs naive batch-1 (auto, "
                  f"unbatched): {engine_vs_naive:.2f}x")
        ss = traffic["steady_state"]
        print(f"steady state: {ss['buckets_warmed']} buckets warmed, "
              f"{ss['compiles_total']} compiles, "
              f"{ss['compiles_under_traffic']} under traffic")
        bt = traffic["snapshot"]["batches"]
        print(f"traffic: buckets {bt['per_bucket']} | padding "
              f"{bt['pad_row_fraction']:.1%}")
    if write:
        write_bench(BENCH_PATH, result, verbose=verbose)
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: xla engine, tiny ladder, no sweep")
    ap.add_argument("--check", action="store_true",
                    help="gate: exit nonzero unless the continuous "
                         "scheduler beats the bucket ladder on BOTH "
                         "p99 latency and goodput in the head-to-head")
    args = ap.parse_args()
    result = run(smoke=args.smoke)
    if args.check:
        wins = result["head_to_head"]["continuous_beats_bucket"]
        if not wins["both"]:
            raise SystemExit(
                f"head-to-head gate FAILED: continuous vs bucket "
                f"p99={wins['p99']} goodput={wins['goodput']} "
                f"(both must be True)"
            )
        print("head-to-head gate OK: continuous beats bucket on p99 "
              "and goodput")
