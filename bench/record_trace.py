"""Record a short traced window of the backlog cell, keep its raw
``.xplane.pb`` and print what the trace holds: the planes and lines,
and the device operations with their names and stats. This is how the
trace under ``bench/tests/data/`` was recorded, and how to look at one
by hand before changing ``bench/trace.py``.

    python3 bench/record_trace.py --out trace_out --seconds 0.2
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", default="serve_backlog")
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    import jax
    from jax.profiler import ProfileData

    from bench import run, trace, traffic
    from bench.drivers import serve

    spec = run.load_spec()
    cell = run.make_cell(spec, args.workload, args.seed, args.seconds, True,
                         T_START)
    run.check_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    engine, _, _ = serve.build(cell)
    pool = traffic.image_pool(cell.mix, cell.seed)
    mix = cell.mix
    due, sizes = traffic.schedule(mix, cell.seed, args.seconds)
    refill = traffic.refill_sizes(mix, cell.seed)
    win = serve.Window(engine, True)
    win.drive(traffic.PoolCursor(pool), due, sizes, mix["keep_queued"],
              refill, 0.5)   # settle
    win = serve.Window(engine, True)
    out = pathlib.Path(args.out)
    tmp = out / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        win.drive(traffic.PoolCursor(pool), due, sizes, mix["keep_queued"],
                  refill, args.seconds)
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp))
    shutil.copy(path, out / "window.xplane.pb")
    print(json.dumps({"xplane_bytes": pathlib.Path(path).stat().st_size,
                      "dispatches": len(win.requests)}))
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            names = {}
            for ev in line.events:
                e = names.setdefault(ev.name, {"n": 0, "ns": 0,
                                               "stats": {}})
                e["n"] += 1
                e["ns"] += ev.duration_ns
                for k, v in ev.stats:
                    if isinstance(v, str) and len(e["stats"]) < 12:
                        e["stats"][k] = v[:160]
            top = sorted(names.items(), key=lambda kv: -kv[1]["ns"])[:25]
            lines[line.name] = top
        print(json.dumps({"plane": plane.name, "lines": lines})[:20000])
    s = trace.reduce(path, devices=[d.id for d in cell.devices()],
                     host_spans=win.spans.items, window_perf=(win.t0, win.t1))
    print(json.dumps({"busy_s": s.busy_s, "window_s": s.window_s,
                      "breakdown": s.breakdown()}))


if __name__ == "__main__":
    main()
