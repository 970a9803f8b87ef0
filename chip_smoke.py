"""Chip smoke: the packed CIFAR BNN serving path, end to end, on a TPU.

Serves the committed trained checkpoint (``tests/golden/bnn_trained_ckpt.npz``)
through the repo's normal entry points — ``pack_trained_params``, then
``ContinuousServingEngine`` and ``ServingEngine`` (``warmup``, ``submit``,
``step``, ``drain``, ``take``) — on every serving engine, and takes three
STE training steps. It fails (exit code 1, no result line) unless:

* JAX's first device is a TPU;
* every request of a seeded ragged burst is served, with no expired or
  failed request, no retry and no engine fallback (the resilience ladder
  is not armed here);
* every Pallas engine lowers to compiled TPU kernels (``tpu_custom_call``),
  never to interpret mode, and its logits are bit-identical, request by
  request, to the ``xla`` oracle engine's;
* the ``xla`` engine's logits for the golden images match the committed
  fixture ``tests/golden/bnn_logits.json`` within ``GOLDEN_ATOL`` with the
  same argmax on every image;
* the training loss is finite.

Per-engine compile seconds, executors compiled, requests served and the
largest absolute difference from the fixture go to stdout as information;
the last line is one JSON object: ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the mesh phase: the same burst through
``make_serving_mesh(4)`` on the ``megakernel`` engine and through the same
engine on one chip, bit-identical, with the batch sharded over all four.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
CHECKPOINT = ROOT / "tests" / "golden" / "bnn_trained_ckpt.npz"
FIXTURE = ROOT / "tests" / "golden" / "bnn_logits.json"

SEED = 0
N_REQUESTS = 12
MAX_IMAGES = 8
# Largest |logit - fixture| accepted for the xla engine on the chip. The
# binary layers are integer-exact on every backend, so what may move is
# float rounding: the first conv (float32-precision dot, another
# accumulation order) and the BatchNorm / bias arithmetic on [N, 10]
# logits of magnitude < 4, a few float32 ulps (2.4e-7 each). A single
# flipped +-1 activation anywhere changes an integer dot by 2 and a logit
# by orders of magnitude more than this bound.
GOLDEN_ATOL = 1e-5

# (engine, conv_impl); "xla" first: it is the oracle every other engine is
# compared with.
ENGINES = (
    ("xla", "im2col"),
    ("megakernel", "im2col"),
    ("xnor", "im2col"),
    ("xnor", "direct"),
    ("megakernel_xla", "im2col"),
)
PALLAS_ENGINES = ("megakernel", "xnor")


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def burst(np, n_requests: int = N_REQUESTS) -> list:
    """The seeded ragged burst: ``n_requests`` requests of 1..MAX_IMAGES
    standard-normal 32x32x3 images."""
    rng = np.random.default_rng(SEED)
    return [
        rng.standard_normal(
            (int(rng.integers(1, MAX_IMAGES + 1)), 32, 32, 3)
        ).astype(np.float32)
        for _ in range(n_requests)
    ]


def golden(np):
    data = json.loads(FIXTURE.read_text())
    want = np.array(
        [[float.fromhex(v) for v in row] for row in data["logits_hex"]],
        np.float32,
    )
    rng = np.random.default_rng(data["image_seed"])
    images = rng.standard_normal(
        (want.shape[0], 32, 32, 3)).astype(np.float32)
    return images, want


def serve(eng, requests: list) -> tuple[list, dict]:
    """Warm the engine's ladder, push the requests through its loop and
    return (per-request logits, facts); any unserved request fails."""
    t0 = time.perf_counter()
    compiled = eng.warmup()
    compile_s = time.perf_counter() - t0
    rids = []
    for imgs in requests:
        rids.append(eng.submit(imgs))
        eng.step()
    eng.drain()
    from repro.serve import is_error

    out = []
    for rid in rids:
        got = eng.take(rid)
        check(got is not None and not is_error(got),
              f"request {rid} was not served: {got!r}")
        out.append(got)
    snap = eng.snapshot()
    req, disp = snap["requests"], snap["dispatch"]
    check(req["failed"] == 0 and req["expired"] == 0,
          f"{req['failed']} failed, {req['expired']} expired requests")
    check(disp["retries"] == 0, f"{disp['retries']} dispatch retries")
    check(disp["fallbacks"] == 0 and not snap["degraded"],
          f"engine fallbacks {disp['fallbacks']} "
          f"({disp['engine_path']}), degraded={snap['degraded']}")
    return out, {"compile_s": compile_s, "executors": compiled,
                 "served": req["completed"]}


def kernel_calls(eng, packed, jnp) -> int:
    """Compiled Pallas kernels in one executor's program: interpret mode
    lowers a kernel to plain HLO, a TPU compile to ``tpu_custom_call``."""
    n = eng.extents[-1] if hasattr(eng, "extents") else eng.batcher.buckets[-1]
    fn = eng.executors.get(n)
    x = jnp.zeros((n, 32, 32, 3), jnp.float32)
    return fn.lower(packed, x).as_text().count("tpu_custom_call")


def single_chip(np, jax, jnp) -> None:
    from repro.core.bnn import load_binary_checkpoint, pack_trained_params
    from repro.serve import (ContinuousServingEngine, RetryPolicy,
                             ServingEngine)
    from repro.train.bnn_trainer import BNNTrainerConfig, train_bnn

    params = load_binary_checkpoint(str(CHECKPOINT))
    packed = pack_trained_params(params)
    gold_images, gold_want = golden(np)
    requests = burst(np) + [gold_images]
    gold_idx = len(requests) - 1
    no_retry = RetryPolicy(max_attempts=1)

    oracle: dict = {}
    for scheduler in ("continuous", "bucket"):
        for engine, conv_impl in ENGINES:
            p = packed["megakernel" if engine.startswith("megakernel")
                       else "fused"]
            if scheduler == "continuous":
                eng = ContinuousServingEngine(
                    p, engine=engine, conv_impl=conv_impl,
                    max_rows=MAX_IMAGES, retry=no_retry)
            else:
                eng = ServingEngine(
                    p, engine=engine, conv_impl=conv_impl,
                    buckets=(1, 4, 8), retry=no_retry)
            logits, facts = serve(eng, requests)
            calls = kernel_calls(eng, p, jnp)
            if engine in PALLAS_ENGINES:
                check(calls > 0, f"{engine}: no compiled TPU kernel")
            if engine == "xla":
                oracle[scheduler] = logits
            else:
                for i, (got, want) in enumerate(
                        zip(logits, oracle[scheduler])):
                    check(np.array_equal(got, want),
                          f"{scheduler}/{engine}/{conv_impl}: request {i} "
                          "differs from the xla engine")
            diff = float(np.max(np.abs(logits[gold_idx] - gold_want)))
            print(f"engine {engine:14s} conv={conv_impl:6s} "
                  f"scheduler={scheduler:10s} compile_s="
                  f"{facts['compile_s']:.2f} executors={facts['executors']} "
                  f"served={facts['served']} kernels={calls} "
                  f"max_abs_diff_vs_fixture={diff:.3e}", flush=True)
            if engine == "xla":
                check(diff <= GOLDEN_ATOL,
                      f"xla logits differ from the fixture by {diff:.3e} "
                      f"> {GOLDEN_ATOL:.0e}")
                check(np.array_equal(np.argmax(logits[gold_idx], -1),
                                     np.argmax(gold_want, -1)),
                      "xla argmax differs from the fixture")

    t0 = time.perf_counter()
    result = train_bnn(BNNTrainerConfig(steps=3, batch=128, eval_batches=1))
    losses = result.history["loss"]
    check(len(losses) == 3 and all(math.isfinite(v) for v in losses),
          f"training losses {losses}")
    print(f"train steps=3 batch=128 losses={losses} "
          f"seconds={time.perf_counter() - t0:.2f}", flush=True)


def four_chips(np, jax, jnp) -> None:
    from repro.core.bnn import (bnn_serve_fn, load_binary_checkpoint,
                                pack_trained_params)
    from repro.launch.mesh import make_serving_mesh
    from repro.serve import ContinuousServingEngine, RetryPolicy

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 chips, JAX sees {len(jax.devices())}")
    mesh = make_serving_mesh(4)
    packed = pack_trained_params(
        load_binary_checkpoint(str(CHECKPOINT)))["megakernel"]
    requests = burst(np)
    no_retry = RetryPolicy(max_attempts=1)
    results = {}
    for name, m in (("mesh4", mesh), ("one_chip", None)):
        eng = ContinuousServingEngine(
            packed, engine="megakernel", max_rows=MAX_IMAGES, mesh=m,
            retry=no_retry)
        results[name], facts = serve(eng, requests)
        print(f"engine megakernel placement={name} compile_s="
              f"{facts['compile_s']:.2f} executors={facts['executors']} "
              f"served={facts['served']}", flush=True)
    for i, (got, want) in enumerate(zip(results["mesh4"],
                                        results["one_chip"])):
        check(np.array_equal(got, want),
              f"request {i}: 4-chip logits differ from one chip")
    # The batch really spreads: each chip holds its own quarter of the
    # output rows.
    fn = bnn_serve_fn(engine="megakernel", ragged=True, mesh=mesh)
    out = fn(packed, jnp.asarray(np.concatenate(requests)[:8]))
    shards = out.addressable_shards
    devices = {s.device.id for s in shards}
    check(len(devices) == 4 and all(s.data.shape[0] == 2 for s in shards),
          f"output shards {[(s.device.id, s.data.shape) for s in shards]}")
    print(f"mesh output shards: {[(s.device.id, tuple(s.data.shape)) for s in shards]}",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args()
    try:
        import jax

        devices = jax.devices()
        check(devices[0].platform == "tpu",
              f"no TPU: JAX's first device is {devices[0].platform}")
        sys.path.insert(0, str(ROOT / "src"))
        import jax.numpy as jnp
        import numpy as np

        from repro.launch.compile_cache import enable_compile_cache

        hits = {"hits": 0, "misses": 0}

        def on_event(event: str, **kwargs) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                hits["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                hits["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        print(f"compile cache: {enable_compile_cache()}", flush=True)
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips(np, jax, jnp)
        else:
            single_chip(np, jax, jnp)
        print(f"compile cache hits={hits['hits']} misses={hits['misses']} "
              f"seconds={time.perf_counter() - t0:.2f}", flush=True)
    except (SmokeFailure, ImportError, RuntimeError) as err:
        print(f"chip_smoke: FAILED: {type(err).__name__}: {err}",
              file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
