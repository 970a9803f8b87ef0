"""Serving driver: Bi-Real Net-18 behind ``ContinuousServingEngine``.

The serving driver's loop (``bench/drivers/serve.py``: its ``Window``,
the sample it checks and the counters it reads) around another network.
Set-up makes the weights from the seed on the device
(``bench/reference_bireal.py``), packs them with the program's packer
that the configuration names, builds the engine for the served model
``core.bireal.served_model`` and warms the extent classes this cell's
traffic dispatches. The images are uint8 pixels drawn from the seed on
the device. After the window, a sample of the served requests, the
largest among them, is checked against the plain float32 reference:
``logit_gap`` is the mean over the checked images of each image's
widest relative logit gap (``bench/reference_bireal.py``, where the
reason for the mean is given).
"""

from __future__ import annotations

import time

import numpy as np

from bench import reference_bireal, traffic
from bench.drivers.serve import Window, _counters, _delta, _rank, sample


def arch_of(model: dict):
    """The program's architecture for a configuration's ``model``."""
    from repro.core import bireal

    net = reference_bireal.Net.of(model)
    return bireal.BiRealArch(
        image=net.image, stem_width=net.stem_width, widths=net.widths,
        depths=net.depths, classes=net.classes, bn_eps=net.bn_eps)


def build(cell):
    """Set-up: weights, packing, engine, warm-up. Returns the engine, the
    float parameters the check compares against, and the extents."""
    import functools

    import jax

    from repro.core import bireal
    from repro.launch.mesh import make_serving_mesh
    from repro.serve import ContinuousServingEngine

    cfg = cell.config["deployment"]
    arch = arch_of(cell.config["model"])
    params = reference_bireal.make_params(
        cell.seed, reference_bireal.Net.of(cell.config["model"]))
    pack = functools.partial(getattr(bireal, cfg["packer"]), arch=arch)
    packed = jax.jit(pack)(params)
    engine = ContinuousServingEngine(
        packed, engine=cfg["engine"], max_rows=cfg["max_rows"],
        max_wait_s=cfg["max_wait_ms"] / 1e3,
        mesh=make_serving_mesh(cell.chips) if cell.chips > 1 else None,
        model=bireal.served_model(arch, name=cell.config["name"]),
    )
    rows = traffic.dispatch_rows(cell.mix, cfg["max_rows"])
    extents = sorted({engine.executors.extent_of(r) for r in rows})
    engine.executors.warmup(extents)
    return engine, params, extents


def image_pool(mix: dict, seed: int, side: int) -> np.ndarray:
    """The run's images, ``[image_pool, side, side, 3]`` uint8 pixels
    uniform over 0..255, drawn on the device from the seed in one call."""
    import jax
    import jax.numpy as jnp

    from bench.reference import seed_key

    key = jax.random.fold_in(seed_key(seed), 0x1A6E)
    shape = (int(mix["image_pool"]), side, side, 3)
    draw = jax.jit(lambda k: jax.random.bits(k, shape, jnp.uint8))
    return np.asarray(draw(key))


def run(cell) -> dict:
    import jax

    mix, seconds = cell.mix, cell.seconds
    engine, params, extents = build(cell)
    net = reference_bireal.Net.of(cell.config["model"])
    pool = image_pool(mix, cell.seed, net.image)
    cursor = traffic.PoolCursor(pool)
    win = Window(engine, cell.trace)
    due, sizes = traffic.schedule(mix, cell.seed, seconds)
    refill = traffic.refill_sizes(mix, cell.seed)
    setup_s = time.perf_counter() - cell.t_start

    cell.start_trace()
    # one dispatch under the profiler before the window: the first after
    # the profiler starts pays its set-up
    engine.executors.warmup(extents[:1])
    c0 = _counters(engine.stats)
    with cell.window_span():
        win.drive(cursor, due, sizes, int(mix.get("keep_queued", 0)), refill,
                  seconds)
    c1 = _counters(engine.stats)
    cell.stop_trace(win.spans.items, (win.t0, win.t1))
    memory_peak = cell.memory_peak()
    win.drain()

    requests = win.requests
    lat = win.latencies_ms()
    done = [r for r in requests
            if r[0] in win.results and win.finish[r[0]] <= seconds]
    images = sum(r[2] for r in done)
    # A step here runs four 64-row dispatches, ~0.56 s: the window holds
    # ~18 steps, so images counted over the whole window would move in
    # steps of 5.6%. The rate is taken over the window up to the last
    # step that came back inside it.
    until = max((win.finish[r[0]] for r in done), default=seconds)
    e2e = {"p50_latency_ms": _rank(lat, 0.50), "images_s": images / until,
           "setup_s": setup_s}
    window = _delta(c0, c1)
    info = {"p99_latency_ms": _rank(lat, 0.99), "requests": len(requests),
            "images_in_window": images, "window_until_s": until,
            "executors_compiled_in_window": window["executor_compiles"],
            "h2d_bytes_per_dispatch": int(pool[:max(extents)].nbytes)}
    at, took = max(win.steps, key=lambda s: s[1], default=(0.0, 0.0))
    info["step_max_ms"], info["step_max_at_s"] = took * 1e3, at

    # Free the program's state before the reference runs.
    served = win.results
    del engine, win.engine
    jax.clear_caches()
    picked = sample(requests, served, mix["check_requests"], cell.seed)
    checked = np.concatenate([pool[lo:lo + k] for _, lo, k, _ in picked])
    got = np.concatenate([served[rid] for rid, *_ in picked])
    gaps = reference_bireal.logit_gaps(params, net, checked, got)
    info.update(reference_bireal.gap_summary(gaps))
    unserved = sum(1 for r in requests if r[0] not in served)
    checks = [
        {"name": "logit_gap", "value": float(gaps.mean()),
         "limit": cell.config["check"]["logit_gap"],
         "images": int(len(checked))},
        {"name": "unserved", "value": unserved, "limit": 0},
    ]
    return {
        "e2e": e2e, "attempted": len(requests), "failed": len(win.failed),
        "window": {"counters": window}, "checks": checks, "info": info,
        "memory_peak_bytes": memory_peak,
    }
