"""BNN serving driver: run the batched serving engine against synthetic
image traffic and report latency/throughput percentiles.

``--scheduler`` picks the dispatch discipline (both drive the same
modes): ``bucket`` — the PR-4 shape-bucket ladder (pad every dispatch
to a rung); ``continuous`` — the v2 ragged scheduler (DESIGN.md §9:
coalesce real rows up to ``--max-rows``, pad only to a tile-padded
extent class, admission control via ``--max-queue-rows``, SLO-aware
wait via ``--slo-ms``).

Two modes:

* ``--smoke`` (default) — a short fixed burst of ragged requests:
  warms every bucket/extent, verifies per-request logits bit for bit
  against the xla oracle engine at the request's exact shape, prints
  the stats snapshot, and exits non-zero on any mismatch, unserved
  request, retry or engine fallback.
* ``--sustained`` — an open-loop load run: requests with random image
  counts arrive at ``--rate`` req/s for ``--duration`` seconds (real
  clock); the engine's dispatch loop runs in the gaps. Reports p50/p95/
  p99 latency, throughput, goodput (with ``--slo-ms``), pad-row waste
  and compile counts.

``--devices N`` (DESIGN.md §10) scales either scheduler out
data-parallel over a 1-D serving mesh: packed weights replicated on
every device, each dispatch's batch sharded over ``data``. Off-TPU the
devices are simulated — the flag forces
``--xla_force_host_platform_device_count=N`` into ``XLA_FLAGS`` before
the first jax backend touch (so it must not be combined with code that
already initialized jax in-process); on a TPU it takes the host's chips.

  PYTHONPATH=src python -m repro.launch.serve_bnn --smoke
  PYTHONPATH=src python -m repro.launch.serve_bnn --smoke --devices 8
  PYTHONPATH=src python -m repro.launch.serve_bnn --scheduler continuous \
      --sustained --rate 20 --duration 10 --max-images 8 --slo-ms 2500
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bnn import (
    bnn_serve_fn,
    init_bnn_params,
    pack_bnn_params_fused,
    pack_bnn_params_megakernel,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import (
    DEFAULT_BUCKETS,
    ContinuousServingEngine,
    FallbackPolicy,
    QueueFull,
    RetryPolicy,
    ServingEngine,
    is_error,
    load_serving_blocks,
)


def _cpu_backend() -> bool:
    """Whether jax will run on the CPU, decided before its first backend
    touch (the only time a host-device count can still be set):
    ``JAX_PLATFORMS`` names the CPU first, or no TPU runtime is
    installed."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        return platforms.split(",")[0].strip() == "cpu"
    return importlib.util.find_spec("libtpu") is None


def _force_host_devices(n: int) -> None:
    """Simulated scale-out on the CPU: force ``n`` host platform
    devices. Must run before the first jax backend touch; a pre-set
    count in XLA_FLAGS (e.g. the CI leg's environment) wins. On a TPU
    the mesh takes the host's chips (``launch.mesh.make_serving_mesh``
    names them when there are too few)."""
    if n <= 1 or not _cpu_backend():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    )


def build_engine(args, *, clock=time.monotonic) -> ServingEngine:
    mesh = None
    if args.devices > 1:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.devices)
        print(f"serving mesh: {args.devices} devices, 1-D data axis "
              f"(weights replicated, batch sharded)")
    params = init_bnn_params(jax.random.PRNGKey(args.seed))
    if args.engine.startswith("megakernel"):
        # one-launch-per-stage executors (DESIGN.md §8) take the
        # pre-stacked megakernel params; conv_impl is direct-path by
        # construction and ignored.
        fused = pack_bnn_params_megakernel(params)
    else:
        fused = pack_bnn_params_fused(params)
    blocks = "auto"
    if args.blocks == "tuned":
        # deployment config saved by benchmarks/serving.py (or any
        # tune_serving_blocks run) in the autotune cache. The tuner may
        # have run at any bucket of the ladder (the benchmark tunes at
        # its largest MEASURED bucket), so probe largest-first and say
        # which entry — if any — was found.
        for b in sorted(args.buckets, reverse=True):
            blocks = load_serving_blocks(args.engine, args.conv_impl, b)
            if blocks != "auto":
                print(f"using tuned serving config for bucket {b}: "
                      f"{blocks}")
                break
        else:
            print("no tuned serving config in the autotune cache for "
                  f"engine={args.engine} conv_impl={args.conv_impl} "
                  f"buckets={args.buckets}; falling back to 'auto'")
    slo_s = args.slo_ms / 1e3 if args.slo_ms is not None else None
    deadline_s = (args.deadline_ms / 1e3
                  if args.deadline_ms is not None else None)
    # --max-retries counts RE-dispatches; the policy counts total
    # attempts (first dispatch included).
    retry = RetryPolicy(max_attempts=args.max_retries + 1)
    fallback = None
    if args.fallback == "on":
        # Arm the bit-identical demotion ladder: hold both param
        # packings so every SERVE_FALLBACKS rung is reachable.
        fallback = FallbackPolicy(
            fused_params=pack_bnn_params_fused(params),
            mega_params=(fused if args.engine.startswith("megakernel")
                         else None),
        )
    if args.scheduler == "continuous":
        return ContinuousServingEngine(
            fused,
            engine=args.engine,
            conv_impl=args.conv_impl,
            blocks=blocks,
            max_rows=args.max_rows,
            max_wait_s=args.max_wait_ms / 1e3,
            max_queue_rows=args.max_queue_rows,
            slo_s=slo_s,
            mesh=mesh,
            deadline_s=deadline_s,
            retry=retry,
            fallback=fallback,
            clock=clock,
        )
    eng = ServingEngine(
        fused,
        engine=args.engine,
        conv_impl=args.conv_impl,
        blocks=blocks,
        buckets=args.buckets,
        max_wait_s=args.max_wait_ms / 1e3,
        mesh=mesh,
        deadline_s=deadline_s,
        retry=retry,
        fallback=fallback,
        clock=clock,
    )
    # SLO is a measurement concern, not a policy one, for the bucket
    # ladder — arm the goodput accounting so head-to-head runs compare
    # like with like.
    eng.stats.slo_s = slo_s
    return eng


def _random_request(rng, max_images: int) -> np.ndarray:
    """One synthetic request: U{1..max_images} random images — the ONE
    traffic distribution both smoke and sustained modes draw from."""
    n = int(rng.integers(1, max_images + 1))
    return rng.normal(size=(n, 32, 32, 3)).astype(np.float32)


def _random_requests(rng, count: int, max_images: int) -> list[np.ndarray]:
    return [_random_request(rng, max_images) for _ in range(count)]


def run_smoke(args) -> dict:
    eng = build_engine(args)
    t0 = time.monotonic()
    n_compiled = eng.warmup()
    t_warm = time.monotonic() - t0
    shapes = (eng.extents if args.scheduler == "continuous"
              else eng.batcher.buckets)
    kind = "extent" if args.scheduler == "continuous" else "bucket"
    print(f"warmup: {n_compiled} {kind} executors compiled "
          f"({', '.join(map(str, shapes))}) in {t_warm:.1f}s")

    rng = np.random.default_rng(args.seed)
    requests = _random_requests(rng, args.requests, args.max_images)
    rids = []
    for imgs in requests:
        rids.append(eng.submit(imgs))
        eng.step()
    eng.drain()

    # Verify the engine's core contract on the smoke traffic: per-request
    # logits are bit-identical to the xla oracle engine running that
    # request's images alone (exact shape, no batching, no kernels).
    oracle = bnn_serve_fn(engine="xla")
    oracle_params = pack_bnn_params_fused(
        init_bnn_params(jax.random.PRNGKey(args.seed))
    )
    mismatches = 0
    errored = 0
    for rid, imgs in zip(rids, requests):
        got = eng.take(rid)
        if got is None or is_error(got):
            # unserved, or a terminal resilience marker
            # (deadline/retries): the smoke fails on either below
            errored += 1
            continue
        want = np.asarray(oracle(oracle_params, jnp.asarray(imgs)))
        if not np.array_equal(got, want):
            mismatches += 1
    snap = eng.snapshot()
    disp = snap["dispatch"]
    print(f"served {snap['requests']['completed']} requests "
          f"({snap['requests']['images_completed']} images), "
          f"{mismatches} logits mismatches vs the xla oracle, "
          f"{errored} unserved/expired/failed, {disp['retries']} "
          f"retries, {disp['fallbacks']} fallbacks")
    print(json.dumps(snap, indent=2))
    if mismatches or errored or disp["retries"] or disp["fallbacks"]:
        raise SystemExit(
            f"smoke failed: {mismatches} requests diverged from the xla "
            f"oracle, {errored} were not served, {disp['retries']} "
            f"retries, {disp['fallbacks']} engine fallbacks"
        )
    return snap


def run_sustained(args) -> dict:
    eng = build_engine(args)
    eng.warmup()
    rng = np.random.default_rng(args.seed)
    interval = 1.0 / args.rate
    t_end = time.monotonic() + args.duration
    t_next = time.monotonic()
    submitted = 0
    rejected = 0
    while time.monotonic() < t_end:
        now = time.monotonic()
        if now >= t_next:
            try:
                eng.submit(_random_request(rng, args.max_images))
                submitted += 1
            except QueueFull:
                rejected += 1  # admission control shed it (counted in
                               # the snapshot too)
            t_next += interval
        # pop finished logits as we go: a long load run must not
        # accumulate every completed result in engine memory
        for rid in eng.step():
            eng.take(rid)
    for rid in eng.drain():
        eng.take(rid)
    snap = eng.snapshot()
    lat, bat = snap["latency_s"], snap["batches"]
    print(f"sustained[{snap['scheduler']}]: {submitted} requests "
          f"({rejected} rejected) over {args.duration:.0f}s "
          f"at {args.rate}/s target")
    wait = snap["queue_wait_s"]
    print(f"throughput {snap['throughput']['images_per_s']:.1f} img/s | "
          f"latency p50 {lat['p50']*1e3:.0f}ms p95 {lat['p95']*1e3:.0f}ms "
          f"p99 {lat['p99']*1e3:.0f}ms | queue wait mean "
          f"{wait['mean']*1e3:.1f}ms max {wait['max']*1e3:.1f}ms")
    print(f"dispatch shapes {bat['per_bucket']} | pad-row fraction "
          f"{bat['pad_row_fraction']:.1%} | compiles "
          f"{snap['executors']['compiles']} (steady state: 0 new)")
    if snap["slo"]["slo_s"] is not None:
        print(f"SLO {snap['slo']['slo_s']*1e3:.0f}ms: goodput "
              f"{snap['slo']['goodput_images_per_s']:.1f} img/s "
              f"({snap['slo']['images_within_slo']} images within SLO)")
    req, disp = snap["requests"], snap["dispatch"]
    if (req["expired"] or req["failed"] or disp["retries"]
            or snap["degraded"]):
        print(f"resilience: {req['expired']} expired, {req['failed']} "
              f"failed, {disp['retries']} batch retries, "
              f"{disp['fallbacks']} fallbacks "
              f"({' '.join(disp['engine_path']) or 'none'}), "
              f"{snap['mesh']['shrinks']} mesh shrinks | "
              f"degraded={snap['degraded']}")
    print(json.dumps(snap, indent=2))
    return snap


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", default="megakernel",
                    choices=["xla", "xnor", "megakernel", "megakernel_xla"],
                    help="xla/xnor: per-layer fused chain (pure-XLA "
                         "fallback, CPU-fast / Pallas, interpret "
                         "off-TPU); megakernel/megakernel_xla: one "
                         "launch per network stage (DESIGN.md §8) — "
                         "uses megakernel-packed params and ignores "
                         "--conv-impl")
    ap.add_argument("--conv-impl", default="im2col",
                    choices=["im2col", "direct"])
    ap.add_argument("--scheduler", default="bucket",
                    choices=["bucket", "continuous"],
                    help="bucket: pad-to-rung ladder (DESIGN.md §7); "
                         "continuous: ragged coalescing over tile-"
                         "padded extent classes with admission control "
                         "and SLO-aware wait (DESIGN.md §9)")
    ap.add_argument("--buckets", type=lambda s: tuple(
        int(b) for b in s.split(",")), default=None,
        help="bucket scheduler: comma-separated batch-size ladder "
             "(default: 1,4,8 for smoke, 1,8,32,128 for sustained)")
    ap.add_argument("--max-rows", type=int, default=None,
                    help="continuous scheduler: per-dispatch row budget "
                         "(default: 8 for smoke, 32 for sustained)")
    ap.add_argument("--max-queue-rows", type=int, default=None,
                    help="continuous scheduler: admission-control bound "
                         "on queued rows (default: unbounded)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="latency SLO: arms goodput accounting on both "
                         "schedulers and the continuous scheduler's "
                         "SLO-aware max-wait")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="micro-batcher head-of-line latency bound")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (DESIGN.md §11): past "
                         "it a request completes as DeadlineExceeded "
                         "instead of being served late (default: none)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="re-dispatches of a failed batch before its "
                         "requests complete as RequestFailed")
    ap.add_argument("--fallback", default="off", choices=["on", "off"],
                    help="'on' arms the bit-identical engine demotion "
                         "ladder (SERVE_FALLBACKS) on repeated kernel "
                         "failure")
    ap.add_argument("--blocks", default="auto", choices=["auto", "tuned"],
                    help="'tuned': use the serving config persisted in "
                         "the autotune cache (benchmarks/serving.py "
                         "writes it); 'auto': per-shape resolution")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--sustained", action="store_true",
                      help="open-loop load run")
    mode.add_argument("--smoke", action="store_true",
                      help="short burst + logits verification (default)")
    ap.add_argument("--requests", type=int, default=12,
                    help="smoke: number of requests in the burst")
    ap.add_argument("--rate", type=float, default=10.0,
                    help="sustained: request arrivals per second")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="sustained: seconds of traffic")
    ap.add_argument("--max-images", type=int, default=8,
                    help="images per request ~ U{1..max}")
    ap.add_argument("--devices", type=int, default=1,
                    help="mesh-sharded serving (DESIGN.md §10): shard "
                         "every dispatch data-parallel over N devices "
                         "(weights replicated). Off-TPU forces N "
                         "simulated host devices via XLA_FLAGS")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    _force_host_devices(args.devices)
    enable_compile_cache()
    if args.buckets is None:
        # Smoke keeps the ladder small so warmup + the per-request
        # exact-shape verification forwards stay CI-cheap.
        args.buckets = DEFAULT_BUCKETS if args.sustained else (1, 4, 8)
    if args.max_rows is None:
        args.max_rows = 32 if args.sustained else 8
    if args.sustained:
        run_sustained(args)
    else:
        run_smoke(args)


if __name__ == "__main__":
    main()
