"""Shared benchmark plumbing: one timing protocol, one JSON schema.

Every BENCH writer uses the same three pieces so the perf trajectory is
comparable across PRs:

* :func:`time_fn` — warmup (compile) call, then MEDIAN of ``repeats``
  timed calls. Median, not mean: interpret-mode wall clocks on a small
  shared CPU see GC pauses and noisy neighbors, and a single outlier
  must not be able to flip a CI ``--check`` gate.
* :func:`stamp` — the environment fingerprint (jax version, backend,
  device kind) recorded into every BENCH file, mirroring the autotune
  cache's staleness stamps: a number is only comparable to another
  number measured on the same stack.
* :func:`write_bench` — wraps the payload as ``{"meta": stamp + schema
  version, **payload}`` and writes it at the repo root.
"""

from __future__ import annotations

import json
import pathlib
import time
from typing import Callable

import jax

from repro.launch.compile_cache import enable_compile_cache

BENCH_SCHEMA_VERSION = 1
REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# Only the BENCH writers import this module, before their first
# compile: turn the persistent compilation cache on for all of them.
enable_compile_cache()


def bench_path(name: str) -> pathlib.Path:
    """Repo-root path for ``BENCH_<name>.json``."""
    return REPO_ROOT / f"BENCH_{name}.json"


def stamp() -> dict:
    """Environment fingerprint for a BENCH file's ``meta`` block."""
    try:
        device = jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no devices at all
        device = "unknown"
    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device": device,
    }


def time_fn(fn: Callable, *args, repeats: int = 3):
    """Median wall time of ``fn(*args)`` over ``repeats`` after one
    warmup (compile) call. Returns ``(seconds, last_output)``."""
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], out


def write_bench(path: pathlib.Path, payload: dict, *,
                verbose: bool = True) -> dict:
    """Prepend the ``meta`` stamp, write ``path``, return the full doc."""
    doc = {"meta": stamp(), **payload}
    path.write_text(json.dumps(doc, indent=2) + "\n")
    if verbose:
        print(f"wrote {path}")
    return doc


__all__ = [
    "BENCH_SCHEMA_VERSION",
    "REPO_ROOT",
    "bench_path",
    "stamp",
    "time_fn",
    "write_bench",
]
