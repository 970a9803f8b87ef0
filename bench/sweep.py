"""Find the knee of the open-loop serving cell: offer a ladder of rates
to one engine, one window each, and report for each rate the latency of
the first and second halves of the window's requests and whether every
request came back.

    python3 bench/sweep.py --workload serve_poisson --seed 5 --seconds 4 \\
        --rates 500,1000,2000

The knee is the highest rate at which nothing fails and the queue does
not grow over the window (the second half's p99 stays near the first
half's). The rate of the cell's mix is set at about 0.8 of it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

T_START = time.perf_counter()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve_poisson")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    sys.path[:0] = [str(__import__("pathlib").Path(__file__).resolve()
                        .parents[1] / p) for p in ("src", ".")]
    from bench import run, traffic
    from bench.drivers import serve

    spec = run.load_spec()
    cell = run.make_cell(spec, args.workload, args.seed, args.seconds,
                         False, T_START)
    run.check_chips(cell.chips)
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    engine, _, _ = serve.build(cell)
    pool = traffic.image_pool(cell.mix, cell.seed)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, arrivals=[{"rate_rps": rate, "seconds": 1}])
        due, sizes = traffic.schedule(mix, cell.seed, args.seconds)
        win = serve.Window(engine, False)
        d0 = engine.stats.dispatched_batches
        win.drive(traffic.PoolCursor(pool), due, sizes, 0, iter(()),
                  args.seconds)
        behind = len(win.requests) - len(win.finish)
        win.drain()
        lat = win.latencies_ms()
        half = len(win.requests) // 2

        def q(rs, p):
            v = np.sort([(win.finish[r[0]] - r[3]) * 1e3
                         if r[0] in win.results else float("inf")
                         for r in rs])
            return serve._rank(v, p)
        print(json.dumps({
            "rate_rps": rate, "requests": len(win.requests),
            "failed": len(win.failed),
            "unfinished_at_close": behind,
            "p50_ms": serve._rank(lat, 0.5), "p99_ms": serve._rank(lat, 0.99),
            "p99_first_half_ms": q(win.requests[:half], 0.99),
            "p99_second_half_ms": q(win.requests[half:], 0.99),
            "dispatches": engine.stats.dispatched_batches - d0,
            "late_p99_ms": serve._rank(np.sort(win.late), 0.99) * 1e3,
        }), flush=True)


if __name__ == "__main__":
    main()
