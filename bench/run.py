"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``, whose ``kind`` picks the
driver ``bench/drivers/<kind>.py``) and its traffic mix
(``bench/traffic/<mix>.json``); each per-layer metric is a reader
``bench/metrics/<metric>.py``. The run sets up, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line as its last line of output. With
``--trace 0`` that line carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics. A machine without a TPU, or with fewer chips than
the cell asks for, gets an error and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    pass


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _import_path():
    """Make ``bench`` and the program (``src/``) importable."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


@dataclasses.dataclass
class Cell:
    """What a driver is given: the cell's data and the run's switches,
    plus the hooks that place the profiler and read device memory."""

    workload: str
    config: dict
    mix: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    t_start: float
    trace_summary: object = None
    _trace_dir: Optional[str] = None

    def devices(self):
        import jax
        return jax.devices()[:self.chips]

    def start_trace(self):
        if not self.trace:
            return
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)

    def window_span(self):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        from bench.trace import WINDOW_SPAN
        return jax.profiler.TraceAnnotation(WINDOW_SPAN)

    def stop_trace(self, host_spans, window):
        if not self.trace:
            return
        import jax

        from bench import trace
        jax.profiler.stop_trace()
        try:
            self.trace_summary = trace.reduce(
                trace.find_xplane(self._trace_dir),
                devices=[d.id for d in self.devices()],
                host_spans=host_spans, window_perf=window)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def memory_peak(self) -> int:
        peaks = []
        for d in self.devices():
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer metric reader sees."""

    workload: str
    chips: int
    seconds: float
    model: dict            # the configuration's shapes (bench/counts.py)
    e2e: dict              # the run's end-to-end numbers
    counters: dict         # program counters, deltas over the window
    trace: object          # bench.trace.TraceSummary of the window
    peaks: dict            # bench/peaks.json row of this device


def _read_metric(name: str, ctx: ReaderContext):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def check_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def make_cell(spec: dict, workload: str, seed: int, seconds: float,
              trace: bool, t_start: float) -> Cell:
    from bench import traffic

    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    return Cell(workload=workload, config=config, mix=traffic.load(w["traffic"]),
                chips=int(w["chips"]), seed=seed, seconds=seconds, trace=trace,
                t_start=t_start)


def run_cell(spec: dict, cell: Cell) -> dict:
    """Drive the cell and build its result line."""
    from bench import counts

    driver = importlib.import_module(f"bench.drivers.{cell.config['kind']}")
    out = driver.run(cell)
    dev = cell.devices()[0]
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks) and not out["failed"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"]}
    if not cell.trace:
        metrics = {}
        for m in spec["end_to_end"]:
            if _applies(m, cell.workload):
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        summary = cell.trace_summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = ReaderContext(
            workload=cell.workload, chips=cell.chips, seconds=cell.seconds,
            model=cell.config["model"], e2e=out["e2e"],
            counters=out["window"]["counters"], trace=summary,
            peaks=counts.peaks_for(dev.device_kind))
        metrics = {}
        for m in spec["per_layer"]:
            if not _applies(m, cell.workload):
                continue
            value = _read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["breakdown"] = summary.breakdown()
    line["metrics"] = metrics
    line["device"] = device
    line["info"] = out["info"]
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _import_path()
    spec = load_spec()
    cell = make_cell(spec, args.workload, args.seed, args.seconds,
                     bool(args.trace), T_START)
    import jax

    try:
        check_chips(cell.chips)
    except NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Cache every program, however quick its compile, so a run after the
    # first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    line = run_cell(spec, cell)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
