"""Serving driver: the packed BNN behind ``ContinuousServingEngine``.

Set-up makes the weights from the seed on the device, packs them with the
program's packer that the configuration names, builds the engine (on a
mesh of the cell's chips where it has more than one) and warms only the
extent classes this cell's traffic can dispatch. The window then drives
the engine's own ``submit``/``step``/``take`` loop, in one thread. Before
every ``step`` it submits each timed request that has come due (never
earlier), then tops the queue up to the mix's ``keep_queued``
(``bench/traffic.py``). A request's latency runs from its due time (a
top-up request's is its submission) to the return of the ``step`` call
that handed back its logits, so a dispatch that blocks the loop delays
every request due behind it.

After the window, every request submitted in it is waited for (up to a
minute) and a sample drawn from the seed, the largest requests among it,
is checked against the plain float32 reference (``bench/reference.py``).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import reference, traffic

DRAIN_LIMIT_S = 60.0


class Spans:
    """Host spans ``(name, t0, t1)`` on ``time.perf_counter``, kept in
    memory while a traced window runs."""

    def __init__(self, on: bool):
        self.on = on
        self.items: list = []

    def add(self, name, t0, t1):
        if self.on:
            self.items.append((name, t0, t1))


def _counters(stats) -> dict:
    return {
        "dispatched": stats.dispatched_batches,
        "real_rows": stats.real_rows,
        "padded_rows": stats.padded_rows,
        "per_extent": dict(stats.bucket_dispatches),
        "executor_compiles": stats.executor_compiles,
    }


def _delta(a: dict, b: dict) -> dict:
    out = {k: b[k] - a[k] for k in a if k != "per_extent"}
    out["per_extent"] = {e: c - a["per_extent"].get(e, 0)
                         for e, c in b["per_extent"].items()
                         if c - a["per_extent"].get(e, 0)}
    return out


def build(cell):
    """Set-up: weights, packing, engine, warm-up of the extent classes
    this cell's traffic dispatches. Returns the engine, the float
    parameters the check compares against, and those extents."""
    import jax

    from repro.core import bnn
    from repro.launch.mesh import make_serving_mesh
    from repro.serve import ContinuousServingEngine

    cfg = cell.config["deployment"]
    net = reference.Net.of(cell.config["model"])
    params = reference.make_params(cell.seed, net)
    packed = jax.jit(getattr(bnn, cfg["packer"]))(params)
    mesh = make_serving_mesh(cell.chips) if cell.chips > 1 else None
    engine = ContinuousServingEngine(
        packed, engine=cfg["engine"], max_rows=cfg["max_rows"],
        max_wait_s=cfg["max_wait_ms"] / 1e3, mesh=mesh,
    )
    rows = traffic.dispatch_rows(cell.mix, cfg["max_rows"])
    extents = sorted({engine.executors.extent_of(r) for r in rows})
    engine.executors.warmup(extents)
    return engine, params, extents


class Window:
    """One measured window's requests and what became of them."""

    def __init__(self, engine, trace: bool):
        self.engine = engine
        self.spans = Spans(trace)
        self.requests = []          # (rid, pool offset, images, due_s)
        self.finish, self.results, self.failed = {}, {}, set()
        self.late = []              # seconds each timed submit ran behind
        self.gc_pauses = []         # (generation, seconds) in the window
        self.steps = []             # (start, seconds) of each engine step
        self.t0 = self.t1 = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc_t))

    def collect(self, rids, at):
        """Take the resolved requests' results, stamped ``at``."""
        from repro.serve import is_error

        for rid in rids:
            out = self.engine.take(rid)
            if out is None or is_error(out):
                self.failed.add(rid)
            else:
                self.results[rid] = out
            self.finish[rid] = at

    def _submit(self, cursor, k, due):
        lo, x = cursor.take(int(k))
        self.requests.append((self.engine.submit(x), lo, len(x), due))

    def drive(self, cursor, due, sizes, keep_queued, refill, seconds):
        """Run the window: timed requests ``(due, sizes)`` as they come
        due, then top-ups from ``refill`` to ``keep_queued`` outstanding
        requests, then one engine step; until ``seconds`` have passed.
        Python's collections in the window are timed as they run."""
        gc.callbacks.append(self._on_gc)
        try:
            self._loop(cursor, due, sizes, keep_queued, refill, seconds)
        finally:
            gc.callbacks.remove(self._on_gc)

    def _loop(self, cursor, due, sizes, keep_queued, refill, seconds):
        engine, stats, spans = self.engine, self.engine.stats, self.spans
        i, n = 0, len(due)
        t0 = self.t0 = time.perf_counter()
        while True:
            a = time.perf_counter()
            now = a - t0
            if now >= seconds:
                break
            first = len(self.requests)
            while i < n and due[i] <= now:
                self.late.append(now - due[i])
                self._submit(cursor, sizes[i], due[i])
                i += 1
            while len(self.requests) - len(self.finish) < keep_queued:
                self._submit(cursor, next(refill), now)
            d0 = stats.dispatched_batches
            b = time.perf_counter()
            rids = engine.step()
            t = time.perf_counter()
            self.steps.append((b - t0, t - b))
            if len(self.requests) > first:
                spans.add("submit", a, b)
            if stats.dispatched_batches != d0:
                spans.add("engine.step", b, t)
            self.collect(rids, t - t0)
        self.t1 = time.perf_counter()

    def drain(self):
        """Wait for every request still in flight (up to a minute)."""
        deadline = time.perf_counter() + DRAIN_LIMIT_S
        while (len(self.finish) < len(self.requests)
               and time.perf_counter() < deadline):
            rids = self.engine.drain() or self.engine.step()
            self.collect(rids, time.perf_counter() - self.t0)

    def latencies_ms(self) -> np.ndarray:
        """Due-to-logits latency of every request, ascending; a request
        that failed or never came back counts as infinitely late."""
        lat = [(self.finish[rid] - due) * 1e3
               if rid in self.results else float("inf")
               for rid, _, _, due in self.requests]
        return np.sort(np.asarray(lat))


def run(cell) -> dict:
    import jax

    mix, seconds = cell.mix, cell.seconds
    engine, params, extents = build(cell)
    pool = traffic.image_pool(mix, cell.seed)
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
    e2e = {"p50_latency_ms": _rank(lat, 0.50),
           "images_s": sum(r[2] for r in done) / seconds,
           "setup_s": setup_s}
    # the tails, which the ~0.1 s stalls of the logits' device-to-host
    # copy swing too far from run to run to hold to a bound
    info = {"p95_latency_ms": _rank(lat, 0.95),
            "p99_latency_ms": _rank(lat, 0.99),
            "requests": len(requests),
            "images_in_window": sum(r[2] for r in done),
            "executors_compiled_in_window": (
                c1["executor_compiles"] - c0["executor_compiles"])}
    if win.late:
        info["generator_late_p99_ms"] = _rank(np.sort(win.late), 0.99) * 1e3
    at, took = max(win.steps, key=lambda s: s[1], default=(0.0, 0.0))
    info["step_max_ms"], info["step_max_at_s"] = took * 1e3, at
    info["steps_over_50ms"] = sum(1 for _, d in win.steps if d > 0.05)
    info["gc_collections"] = len(win.gc_pauses)
    info["gc_full_collections"] = sum(1 for g, _ in win.gc_pauses if g == 2)
    info["gc_pause_max_ms"] = max((t for _, t in win.gc_pauses), default=0.0) * 1e3

    # Free the program's state before the reference runs.
    served = win.results
    del engine, win.engine
    jax.clear_caches()
    picked = sample(requests, served, mix["check_requests"], cell.seed)
    images = np.concatenate([pool[lo:lo + k] for _, lo, k, _ in picked])
    got = np.concatenate([served[rid] for rid, *_ in picked])
    unserved = sum(1 for r in requests if r[0] not in served)
    checks = check(cell.config, params, images, got, unserved)
    return {
        "e2e": e2e, "attempted": len(requests), "failed": len(win.failed),
        "window": {"counters": _delta(c0, c1)}, "checks": checks,
        "info": info, "memory_peak_bytes": memory_peak,
    }


def _rank(sorted_values: np.ndarray, q: float) -> float:
    """Nearest-rank quantile of an ascending array."""
    if len(sorted_values) == 0:
        return float("inf")
    k = max(0, int(np.ceil(q * len(sorted_values))) - 1)
    return float(sorted_values[k])


def sample(requests, served, n: int, seed: int) -> list:
    """Up to ``n`` served requests drawn from the seed, the largest
    among them."""
    done = [r for r in requests if r[0] in served]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) % 2**64, 3])
    pick = set(rng.choice(len(done), min(n, len(done)), replace=False))
    pick.add(max(range(len(done)), key=lambda j: done[j][2]))
    return [done[j] for j in sorted(pick)]


def check(config: dict, params, images, got, unserved: int) -> list:
    """The compared numbers, each with its limit: the widest relative
    logit gap of the sampled images' served logits ``got`` against the
    float32 reference, and how many requests submitted in the window
    were not served."""
    net = reference.Net.of(config["model"])
    gaps = reference.logit_gaps(params, net, images, got)
    return [
        {"name": "logit_gap", "value": float(gaps.max()),
         "limit": config["check"]["logit_gap"], "images": int(len(images))},
        {"name": "unserved", "value": unserved, "limit": 0},
    ]
