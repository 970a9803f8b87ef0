"""Serving-level block selection: pick ONE kernel tiling for the whole
deployed engine, tuned for the steady-state (batched) buckets.

The PR-3 autotuner (``kernels/autotune.py``) tunes each GEMM/conv shape
in isolation. A serving deployment wants the complement: a single
``blocks`` config for the engine (the executor cache compiles one
program per bucket; per-layer shapes inside it are fixed by the
bucket), chosen to maximize throughput at the bucket the fleet actually
runs — the largest one, where batching amortizes the per-dispatch fixed
work. ``tune_serving_blocks`` measures whole ``bnn_serve_fn`` forwards
across a small candidate list at that bucket and persists the winner in
the SAME autotune JSON cache (kernel name ``"bnn_serve"``, shape key =
engine/conv_impl/bucket, stamped with jax version + device kind and
ignored on mismatch, exactly like the per-kernel entries). Warmup then
reuses the cached entry via :func:`load_serving_blocks` — steady-state
serving never re-measures.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence, Union

import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.autotune import AUTO, BlockConfig

SERVE_KERNEL = "bnn_serve"

Blocks = Union[str, BlockConfig]


def serving_shape(engine: str, conv_impl: str, bucket: int) -> dict:
    """The autotune-cache shape key for one serving configuration."""
    return {"engine": engine, "conv": conv_impl, "bucket": bucket}


def default_serving_candidates(bucket: int) -> list[Blocks]:
    """Per-shape AUTO plus a few throughput-oriented global tilings.

    The big-``block_n`` entries matter at large buckets: conv GEMM N is
    ``bucket * OH * OW``, so wider N tiles cut grid steps (and their
    per-step overhead) once batching has made N large.
    """
    cands: list[Blocks] = [AUTO]
    for bm, bn, bkw, wg in (
        (512, 512, 64, 32),
        (512, 1024, 64, 64),
        (512, 2048, 64, 32),
        (256, 512, 32, 8),
    ):
        if bn <= max(1024, bucket * 1024):  # don't over-tile tiny buckets
            cands.append(BlockConfig(block_m=bm, block_n=bn, block_kw=bkw,
                                     word_group=wg))
    return cands


def load_serving_blocks(
    engine: str, conv_impl: str, bucket: int
) -> Blocks:
    """Cached serving config for this engine/conv_impl/bucket, or AUTO.

    Entries recorded under a different jax version or device kind are
    ignored by the underlying :func:`kernels.autotune.load_entry`."""
    if not autotune.cache_enabled():
        return AUTO
    cfg = autotune.load_entry(
        SERVE_KERNEL, serving_shape(engine, conv_impl, bucket)
    )
    return cfg if cfg is not None else AUTO


def tune_serving_blocks(
    packed_params: dict,
    bucket: int,
    *,
    engine: str = "xnor",
    conv_impl: str = "im2col",
    candidates: Optional[Iterable[Blocks]] = None,
    repeats: int = 1,
    cache: bool = True,
    timings: Optional[dict] = None,
) -> Blocks:
    """Measure whole-forward wall time per candidate at ``bucket``;
    return (and optionally cache) the fastest config.

    Timing uses the shared :func:`kernels.autotune.time_call` protocol
    (one warmup/compile call, then the mean of ``repeats``). Pass a
    dict as ``timings`` to receive per-candidate seconds keyed by the
    candidate (``"auto"`` or a ``BlockConfig``).
    """
    from repro.core.bnn import bnn_serve_fn  # local: avoid import cycle
    from repro.serve.executor import IMAGE_SHAPE

    def operand():
        return jnp.zeros((bucket,) + IMAGE_SHAPE, jnp.float32)

    cands = list(candidates) if candidates is not None else (
        default_serving_candidates(bucket)
    )
    best, best_t = None, float("inf")
    for blocks in cands:
        fn = bnn_serve_fn(engine=engine, conv_impl=conv_impl, blocks=blocks)
        t = autotune.time_call(lambda: fn(packed_params, operand()), repeats)
        if timings is not None:
            timings[blocks] = t
        if t < best_t:
            best, best_t = blocks, t
    assert best is not None, "empty candidate list"
    if cache and autotune.cache_enabled() and isinstance(best, BlockConfig):
        autotune.save_entry(
            SERVE_KERNEL, serving_shape(engine, conv_impl, bucket), best,
            wall_s=best_t,
        )
    return best


__all__ = [
    "SERVE_KERNEL",
    "serving_shape",
    "default_serving_candidates",
    "load_serving_blocks",
    "tune_serving_blocks",
]
