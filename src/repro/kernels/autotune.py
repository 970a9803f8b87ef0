"""Block-size autotuning for the xnor kernels (DESIGN.md §6).

The broadcast-free accumulator (``kernels/popcount.py``) shrank each
grid step's VMEM footprint ~8-14x, which makes tile choice a real
degree of freedom instead of "whatever fits". This module owns that
choice, in three layers:

1. **VMEM model** — :func:`gemm_step_vmem` / :func:`conv_step_vmem`
   compute the per-grid-step VMEM bytes of each kernel from its block
   shape (both the legacy ``broadcast`` and the ``loop`` formulation,
   so benchmarks can report the reduction).
2. **Heuristic defaults** — :func:`heuristic_gemm_blocks` picks the
   largest TPU-legal tiles (:func:`gemm_blocks_legal`) whose
   double-buffered footprint fits a conservative VMEM budget, clamped
   to the (padded) problem shape; :func:`heuristic_conv_block_d` takes
   the whole output-channel extent. This is what ``block_*="auto"``
   resolves to when no tuned entry exists.
3. **Measured tuning** — :func:`tune` times a kernel wrapper across a
   candidate grid and persists the winner in a JSON cache keyed by
   kernel name + shape. Entries record the jax version and device kind
   and are IGNORED on mismatch (a stale cache can never poison a new
   runtime — the invalidation guard of ISSUE 3).

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``$XDG_CACHE_HOME/repro/autotune.json``, else
``~/.cache/repro/autotune.json``. Set ``REPRO_AUTOTUNE=0`` to bypass
the cache entirely (heuristics only). Cache format (entry keys join
the shape dims in sorted-name order)::

    {"version": 1,
     "entries": {
       "fused_xnor_gemm|kw=128|m=512|n=512": {
         "jax": "0.9.0", "device": "TPU v5 lite",
         "block_m": 256, "block_n": 256, "block_kw": 32,
         "word_group": 8, "wall_s": 0.0123}}}

Every config this module emits is exact by construction: block shape
never changes results (asserted across the candidate grid in
``tests/test_autotune.py``), only speed.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import tempfile
import time
from typing import Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.bitops import PACK_BITS
from repro.kernels.popcount import DEFAULT_WORD_GROUP

AUTO = "auto"
CACHE_VERSION = 1
# Target per-step footprint: ~16 MiB VMEM per TPU core, halved for
# double buffering of the streamed operand/output tiles, halved again
# as headroom for the compiler's own temporaries.
VMEM_BUDGET_BYTES = 4 * 1024 * 1024
_I32 = 4


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One kernel tiling. ``block_m`` doubles as ``block_d`` (the
    output-channel tile) for the direct-conv kernels, which have no
    N/KW tiling of their own."""

    block_m: int = 128
    block_n: int = 128
    block_kw: int = 16
    word_group: int = DEFAULT_WORD_GROUP

    def gemm_kwargs(self) -> dict:
        return {
            "block_m": self.block_m,
            "block_n": self.block_n,
            "block_kw": self.block_kw,
            "word_group": self.word_group,
        }

    def conv_kwargs(self) -> dict:
        return {"block_d": self.block_m, "word_group": self.word_group}


# ---------------------------------------------------------------------------
# VMEM-per-step model
# ---------------------------------------------------------------------------

def gemm_step_vmem(
    bm: int, bn: int, bkw: int, *, fused: bool = False,
    accum: str = "loop", unpack: bool = False,
) -> int:
    """Per-grid-step VMEM bytes of (fused_)xnor_gemm at one tiling.

    ``accum="broadcast"`` models the legacy 3-D ``[bm, bkw, bn]`` xnor
    intermediate; ``"loop"`` models the fori_loop accumulator whose
    only intermediate is one 2-D ``[bm, bn]`` word term.
    ``unpack=True`` models ``unpack_gemm`` instead: the packed weight
    tile unpacks to a ±1 ``[bm, bkw*32]`` tile in VMEM and contracts a
    real f32 activation tile on the MXU — a different (and much
    steeper-in-``bkw``) footprint than the xnor kernels.
    """
    if unpack:
        w = bm * bkw * _I32                        # packed words
        wu = bm * bkw * PACK_BITS * _I32           # unpacked ±1 tile
        x = bkw * PACK_BITS * bn * _I32            # f32 activation tile
        acc = bm * bn * _I32                       # f32 accumulator
        out = bm * bn * _I32
        return w + wu + x + acc + out
    w = bm * bkw * _I32
    x = bkw * bn * _I32
    acc = bm * bn * _I32
    interm = bm * bkw * bn * _I32 if accum == "broadcast" else bm * bn * _I32
    total = w + x + acc + interm
    if fused:
        y = bm * bn * _I32                      # epilogue f32 affine
        out = (bm // PACK_BITS) * bn * _I32     # packed out tile
        ab = 2 * bm * _I32
        total += y + out + ab
    else:
        total += bm * bn * _I32                 # int32 out tile
    return total


def conv_step_vmem(
    hp: int, wp: int, cw: int, block_d: int, kh: int, kw: int, ow: int,
    *, fused: bool = True, accum: str = "loop",
) -> int:
    """Per-grid-step VMEM bytes of the direct-conv kernels."""
    kwords = kh * kw * cw
    xmap = hp * wp * cw * _I32
    w = block_d * kwords * _I32
    xmat = ow * kwords * _I32  # gathered window rows
    interm = (
        block_d * ow * kwords * _I32 if accum == "broadcast"
        else block_d * ow * _I32
    )
    total = xmap + w + xmat + interm
    if fused:
        total += block_d * ow * _I32 + (block_d // PACK_BITS) * ow * _I32
        total += 2 * block_d * _I32
    else:
        total += block_d * ow * _I32
    return total


# ---------------------------------------------------------------------------
# Heuristic defaults (used whenever no tuned cache entry applies)
# ---------------------------------------------------------------------------

def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _m_step(fused: bool) -> int:
    """Smallest legal partial M tile. The kernels read word-major
    weights ``wt [bkw, bm]`` (bm on lanes: a multiple of 128), and the
    fused kernel writes packed ``[bm/32, bn]`` tiles (bm/32 on
    sublanes: a multiple of 8, so bm a multiple of 256)."""
    return 256 if fused else 128


def gemm_blocks_legal(m: int, kw: int, n: int, cfg: BlockConfig, *,
                      fused: bool = False) -> bool:
    """Whether ``cfg`` tiles an ``[M, KW] x [KW, N]`` (fused_)xnor_gemm
    or unpack_gemm the way the TPU lowering requires: each block's last
    two dims a multiple of (8, 128) or the whole padded array dim. The
    operand blocks are ``wt [bkw, bm]``, ``x [bkw, bn]`` and the output
    ``[bm, bn]`` (fused: ``[bm/32, bn]``); a block at least as large as
    its padded dim is clamped to it by :func:`resolve_gemm_blocks`."""
    m_full = _round_up(max(m, 1), PACK_BITS if fused else 8)
    return (
        (cfg.block_m >= m_full or cfg.block_m % _m_step(fused) == 0)
        and (cfg.block_kw >= kw or cfg.block_kw % 8 == 0)
        and cfg.block_n % 128 == 0
    )


def heuristic_gemm_blocks(
    m: int, kw: int, n: int, *, fused: bool = False, unpack: bool = False,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> BlockConfig:
    """Largest TPU-legal tiles fitting ``vmem_budget``, clamped to shape.

    Starts from the loop-formulation ceiling (bm=bn=512, bkw=64 — ~9x
    the old broadcast default's work per step at ~2.6 MiB) and halves
    the largest contributor until the model fits, staying on the tile
    grid :func:`gemm_blocks_legal` checks: ``bm`` is the whole padded M
    or a multiple of 128 (256 when fused), ``bn`` a multiple of 128,
    ``bkw`` all KW words or a multiple of 8. With ``unpack=True`` the
    model charges the in-VMEM unpacked ±1 weight tile, so ``bkw`` lands
    much smaller (each packed K-word is 32 real rows of the MXU
    contraction).
    """
    step = _m_step(fused)
    bm = min(512, _round_up(max(m, 1), PACK_BITS if fused else 8))
    bn = min(512, _round_up(max(n, 1), 128))
    bkw = min(64, max(kw, 1))
    while gemm_step_vmem(bm, bn, bkw, fused=fused, unpack=unpack) > vmem_budget:
        if bm >= bn and bm > step:
            bm = max(step, bm // 2 // step * step)
        elif bn > 128:
            bn = max(128, bn // 2)
        elif bkw > 8:
            bkw = max(8, bkw // 2 // 8 * 8)
        else:
            break  # floors reached; nothing left to shrink
    return BlockConfig(block_m=bm, block_n=bn, block_kw=bkw)


@dataclasses.dataclass(frozen=True)
class ResidualTile:
    """The output positions one grid step of the residual block conv
    covers (DESIGN.md §5.1): ``rows`` output rows of each of ``images``
    whole images, ``ow_pad`` columns a row (OW up to a multiple of 8,
    so the rows merge onto lanes cleanly); ``bands`` row bands tile an
    image (``bands * rows >= OH``, the last band ragged when it is
    larger)."""
    images: int
    rows: int
    ow_pad: int
    bands: int

    @property
    def lanes(self) -> int:
        return self.images * self.rows * self.ow_pad


# Int32 elements of one residual step's [block_d, positions] tile
# (512 KiB). D counts at its 128-lane padding: the f32 shortcut and
# output blocks put D on lanes, so a D = 64 stage gets no more
# positions than a D = 128 one.
RESIDUAL_TILE_ELEMS = 1 << 17


def residual_conv_tile(n: int, oh: int, ow: int, d: int) -> ResidualTile:
    """Positions a residual block-conv step covers, from the shapes
    alone: ``n`` images of ``oh x ow`` outputs, D tile ``d``.

    A step gets ``RESIDUAL_TILE_ELEMS / d`` lane positions (at least
    128). An image that has more positions is cut into the fewest bands
    of equal rows that fit; smaller images go several to a step, the
    largest divisor of ``n`` that fits, so no step holds pad images.
    """
    ow_pad = _round_up(ow, 8)
    lanes = max(128, RESIDUAL_TILE_ELEMS // _round_up(max(d, 1), 128))
    per_image = oh * ow_pad
    if per_image > lanes:
        bands = -(-oh // max(1, lanes // ow_pad))
        return ResidualTile(1, -(-oh // bands), ow_pad, bands)
    fit = min(n, lanes // per_image)
    images = max(i for i in range(1, fit + 1) if n % i == 0)
    return ResidualTile(images, oh, ow_pad, 1)


def heuristic_conv_block_d(d: int) -> BlockConfig:
    """Output-channel tile for the direct-conv kernels: all of D. The
    output tile ``[1, 1, OW, block_d/32]`` (fused) / ``[..., block_d]``
    puts the channel words on lanes, where a partial tile would have to
    be a multiple of 128 words (4096 channels); a CIFAR-scale layer's
    whole filter bank is a few hundred KiB of VMEM
    (:func:`conv_step_vmem`)."""
    return BlockConfig(block_m=_round_up(max(d, 1), PACK_BITS))


# ---------------------------------------------------------------------------
# Persistent tuning cache
# ---------------------------------------------------------------------------

def cache_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return pathlib.Path(env)
    base = os.environ.get("XDG_CACHE_HOME")
    root = pathlib.Path(base) if base else pathlib.Path.home() / ".cache"
    return root / "repro" / "autotune.json"


def _device_kind() -> str:
    try:
        return jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no devices at all
        return "unknown"


def _entry_key(kernel: str, shape: dict) -> str:
    parts = "|".join(f"{k}={shape[k]}" for k in sorted(shape))
    return f"{kernel}|{parts}"


# In-process memo of parsed cache files keyed by (path, mtime_ns, size)
# — load_entry runs on every "auto"-resolved kernel call, and re-reading
# the JSON from disk each time would put file I/O inside timed regions.
_read_memo: dict = {}


def _load_raw(path: Optional[pathlib.Path] = None) -> dict:
    path = path or cache_path()
    empty = {"version": CACHE_VERSION, "entries": {}}
    try:
        stat = path.stat()
        memo_key = (str(path), stat.st_mtime_ns, stat.st_size)
        cached = _read_memo.get(memo_key)
        if cached is not None:
            return cached
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return empty
    if (
        not isinstance(data, dict)
        or data.get("version") != CACHE_VERSION
        or not isinstance(data.get("entries"), dict)
    ):
        data = empty  # malformed file: ignored, overwritten on next save
    _read_memo.clear()  # only the latest file version is worth keeping
    _read_memo[memo_key] = data
    return data


def save_entry(
    kernel: str, shape: dict, config: BlockConfig, *,
    wall_s: Optional[float] = None, path: Optional[pathlib.Path] = None,
) -> None:
    """Persist one tuned config (stamped with jax version + device)."""
    path = path or cache_path()
    data = _load_raw(path)
    data["entries"][_entry_key(kernel, shape)] = {
        "jax": jax.__version__,
        "device": _device_kind(),
        "block_m": config.block_m,
        "block_n": config.block_n,
        "block_kw": config.block_kw,
        "word_group": config.word_group,
        **({"wall_s": wall_s} if wall_s is not None else {}),
    }
    path.parent.mkdir(parents=True, exist_ok=True)
    # Atomic publish: a UNIQUE temp file in the same directory, fsync'd,
    # then os.replace — concurrent CI/benchmark runs each stage their
    # own temp (a shared fixed ".tmp" name lets two writers interleave
    # into one file), and a reader can never observe a torn write: it
    # sees either the old cache or the new one. A crash mid-write
    # leaves at most a stray temp file, never a corrupt cache (and a
    # corrupt cache would be IGNORED by ``_load_raw``, not fatal).
    fd, tmp_name = tempfile.mkstemp(
        dir=path.parent, prefix=path.name + ".", suffix=".tmp"
    )
    try:
        with os.fdopen(fd, "w") as f:
            f.write(json.dumps(data, indent=2, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp_name, path)
    except BaseException:
        try:
            os.unlink(tmp_name)
        except OSError:
            pass
        raise


def load_entry(
    kernel: str, shape: dict, *, path: Optional[pathlib.Path] = None
) -> Optional[BlockConfig]:
    """Look up a tuned config. Returns None when absent OR stale —
    entries recorded under a different jax version or device kind are
    ignored (the cache-invalidation guard), never re-served.
    """
    entry = _load_raw(path)["entries"].get(_entry_key(kernel, shape))
    if not isinstance(entry, dict):
        return None
    if entry.get("jax") != jax.__version__:
        return None
    if entry.get("device") != _device_kind():
        return None
    try:
        return BlockConfig(
            block_m=int(entry["block_m"]),
            block_n=int(entry["block_n"]),
            block_kw=int(entry["block_kw"]),
            word_group=int(entry.get("word_group", DEFAULT_WORD_GROUP)),
        )
    except (KeyError, TypeError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Measured block-size search
# ---------------------------------------------------------------------------

def default_gemm_candidates(
    m: int, kw: int, n: int, *, fused: bool = False
) -> list[BlockConfig]:
    """A small, shape-clamped candidate grid around the heuristic.

    ``word_group`` is swept alongside the tile dims: the mid-size tile
    appears with a smaller and a full-unroll group (``group >= bkw``
    compiles to a pure static walk with no fori_loop / dynamic slice —
    see ``kernels/popcount.py``).
    """
    seen, out = set(), []
    base = [
        (128, 128, 16, DEFAULT_WORD_GROUP),
        (256, 128, 16, DEFAULT_WORD_GROUP),
        (128, 256, 16, DEFAULT_WORD_GROUP),
        (256, 256, 32, DEFAULT_WORD_GROUP),
        (256, 256, 32, 4),
        (256, 256, 32, 32),   # full unroll: no fori_loop in-kernel
        (512, 256, 64, DEFAULT_WORD_GROUP),
        (256, 512, 64, DEFAULT_WORD_GROUP),
    ]
    m_mult = PACK_BITS if fused else 8
    for bm, bn, bkw, grp in base:
        cfg = BlockConfig(
            block_m=min(bm, _round_up(max(m, 1), m_mult)),
            block_n=min(bn, _round_up(max(n, 1), 128)),
            block_kw=min(bkw, max(kw, 1)),
            word_group=grp,
        )
        if cfg not in seen and gemm_blocks_legal(m, kw, n, cfg, fused=fused):
            seen.add(cfg)
            out.append(cfg)
    return out


def time_call(fn: Callable[[], jnp.ndarray], repeats: int) -> float:
    """Mean wall time of ``fn()`` over ``repeats`` after one warmup
    (compile) call. The one timing protocol shared by :func:`tune` and
    the benchmark sweeps."""
    jax.block_until_ready(fn())  # compile / warm up
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def rand_packed(key, shape: tuple[int, ...]) -> jnp.ndarray:
    """Uniform random packed int32 words (benchmark/tuning operands)."""
    info = jnp.iinfo(jnp.int32)
    return jax.random.randint(key, shape, info.min, info.max,
                              dtype=jnp.int32)


def tune(
    fn: Callable[..., jnp.ndarray],
    shapes: tuple[int, int, int],
    *,
    fused: bool = False,
    candidates: Optional[Iterable[BlockConfig]] = None,
    repeats: int = 2,
    cache: bool = True,
    kernel: Optional[str] = None,
    timings: Optional[dict] = None,
) -> BlockConfig:
    """Measure ``fn`` across block configs and return the fastest.

    ``fn`` is a padded GEMM wrapper with the ``kernels.ops`` signature:
    ``fn(wp, xp, k_bits, *, block_m, block_n, block_kw, word_group)``
    (plus ``(a, b)`` positionals when ``fused=True``). ``shapes`` is the
    UNPACKED problem ``(m, k, n)``; operands are synthesized here. The
    winner is persisted to the JSON cache (unless ``cache=False`` or
    ``REPRO_AUTOTUNE=0``) so later ``block_*="auto"`` calls on the same
    shape, device and jax version reuse it without re-measuring. Pass a
    dict as ``timings`` to receive the per-candidate wall times.
    """
    m, k, n = shapes
    kw = -(-k // PACK_BITS)
    kernel = kernel or getattr(fn, "__name__", "gemm")
    key = jax.random.PRNGKey(m * 131 + k * 31 + n)
    wp = rand_packed(jax.random.fold_in(key, 0), (m, kw))
    xp = rand_packed(jax.random.fold_in(key, 1), (kw, n))
    extra = ()
    if fused:
        a = jax.random.normal(jax.random.fold_in(key, 2), (m,))
        b = jax.random.normal(jax.random.fold_in(key, 3), (m,))
        extra = (a, b)

    cands = list(candidates) if candidates is not None else (
        default_gemm_candidates(m, kw, n, fused=fused)
    )
    best_cfg, best_t = None, float("inf")
    for cfg in cands:
        t = time_call(
            lambda cfg=cfg: fn(wp, xp, k, *extra, **cfg.gemm_kwargs()),
            repeats,
        )
        if timings is not None:
            timings[cfg] = t
        if t < best_t:
            best_cfg, best_t = cfg, t
    assert best_cfg is not None, "empty candidate list"
    if cache and cache_enabled():
        save_entry(
            kernel, {"m": m, "kw": kw, "n": n}, best_cfg, wall_s=best_t
        )
    return best_cfg


# ---------------------------------------------------------------------------
# Megakernel: weights-resident VMEM model + joint batch-tile search
# ---------------------------------------------------------------------------

MEGAKERNEL_KERNEL = "bnn_megakernel"
# The megakernel's weights are fetched ONCE and stay resident (constant
# block index) — they are not double-buffered, so only the streamed
# batch tiles pay the 2x. Budget: 16 MiB VMEM minus ~4 MiB compiler
# headroom for the whole residency (weights + scratch + intermediates).
MEGAKERNEL_VMEM_BUDGET = 12 * 1024 * 1024


def megakernel_vmem(
    l: int, m_max: int, kw_max: int, block_n: int, *, final_m: int = 0
) -> int:
    """Whole-launch VMEM bytes of ``megakernel_chain`` at one batch
    tile: resident stacked weights/affines + the ping-pong scratch pair
    + the per-layer intermediates (popcount word term, int32 acc, f32
    epilogue) + the in/out batch tiles."""
    kw_act = max(kw_max, m_max // PACK_BITS)
    weights = l * m_max * kw_max * _I32 + 2 * l * m_max * _I32
    scratch = 2 * kw_act * block_n * _I32          # ping-pong pair
    interm = 3 * m_max * block_n * _I32            # word term + acc + y
    x_tile = kw_act * block_n * _I32
    fin = final_m * kw_act * _I32 if final_m else 0
    out = max(final_m, m_max // PACK_BITS) * block_n * _I32
    return weights + scratch + interm + x_tile + fin + out


def heuristic_megakernel_block_n(
    l: int, m_max: int, kw_max: int, n: int, *, final_m: int = 0,
    vmem_budget: int = MEGAKERNEL_VMEM_BUDGET,
) -> int:
    """Largest lane-aligned batch tile whose modeled whole-launch
    residency fits ``vmem_budget`` (floor: one 128-lane tile — the
    weights are resident regardless, so shrinking below a lane tile
    buys nothing)."""
    bn = min(512, _round_up(max(n, 1), 128))
    while (
        megakernel_vmem(l, m_max, kw_max, bn, final_m=final_m) > vmem_budget
        and bn > 128
    ):
        bn = max(128, bn // 2)
    return bn


def megakernel_shape(
    l: int, m_max: int, kw_max: int, n: int, final_m: int = 0
) -> dict:
    """The autotune-cache shape key for one megakernel chain."""
    return {"l": l, "m": m_max, "kw": kw_max, "n": n, "mf": final_m}


def resolve_megakernel_block_n(
    l: int, m_max: int, kw_max: int, n: int,
    block_n, word_group, *, final_m: int = 0,
) -> tuple[int, int]:
    """``"auto"`` -> tuned ``bnn_megakernel`` cache entry (same
    jax-version/device staleness guard as every other kernel) ->
    weights-resident heuristic; then clamp to the padded batch."""
    if _is_auto(block_n) or _is_auto(word_group):
        cfg = None
        if cache_enabled():
            cfg = load_entry(
                MEGAKERNEL_KERNEL, megakernel_shape(l, m_max, kw_max, n,
                                                    final_m)
            )
        if cfg is not None:
            block_n = cfg.block_n if _is_auto(block_n) else block_n
            word_group = (
                cfg.word_group if _is_auto(word_group) else word_group
            )
        else:
            if _is_auto(block_n):
                block_n = heuristic_megakernel_block_n(
                    l, m_max, kw_max, n, final_m=final_m
                )
            if _is_auto(word_group):
                word_group = DEFAULT_WORD_GROUP
    block_n = max(1, min(int(block_n), _round_up(max(n, 1), 128)))
    return block_n, int(word_group)


def tune_block_n(
    kernel: str,
    shape: dict,
    fn: Callable[[int], jnp.ndarray],
    candidates: Sequence[int] = (128, 256, 512),
    *,
    repeats: int = 2,
    cache: bool = True,
    timings: Optional[dict] = None,
) -> int:
    """Joint batch-tile search for grid-tiles-the-batch kernels
    (megakernel chains): time ``fn(block_n)`` across ``candidates``,
    persist the winner under ``kernel``/``shape`` in the existing JSON
    cache (``block_n`` field of the entry; the staleness stamps and
    atomic write are shared with every other kernel), return it.
    """
    best_bn, best_t = None, float("inf")
    for bn in candidates:
        t = time_call(lambda bn=bn: fn(bn), repeats)
        if timings is not None:
            timings[bn] = t
        if t < best_t:
            best_bn, best_t = bn, t
    assert best_bn is not None, "empty candidate list"
    if cache and cache_enabled():
        save_entry(kernel, shape, BlockConfig(block_n=best_bn),
                   wall_s=best_t)
    return best_bn


def megakernel_block_kwargs(blocks) -> dict:
    """Config-surface helper for the megakernel wrappers: a ``blocks``
    value (``"auto"`` or a :class:`BlockConfig`) -> the keyword
    arguments ``ops.megakernel_chain`` / ``ops.megakernel_conv_stage``
    understand (``block_n`` tiles the batch; ``word_group`` is shared
    with every popcount kernel)."""
    if _is_auto(blocks) or blocks is None:
        return {}
    if isinstance(blocks, BlockConfig):
        return {"block_n": blocks.block_n, "word_group": blocks.word_group}
    raise TypeError(f"blocks must be 'auto' or BlockConfig, got {blocks!r}")


# ---------------------------------------------------------------------------
# "auto" resolution for the kernels.ops wrappers
# ---------------------------------------------------------------------------

def _is_auto(v) -> bool:
    return isinstance(v, str) and v == AUTO


def resolve_gemm_blocks(
    kernel: str, m: int, kw: int, n: int,
    block_m, block_n, block_kw, word_group,
    *, fused: bool = False, unpack: bool = False,
) -> tuple[int, int, int, int]:
    """Turn possibly-``"auto"`` block requests into concrete ints.

    Order: tuned cache entry (if valid for this jax/device) -> heuristic
    VMEM-budget defaults. Every resolved (and every explicitly
    requested) block is then clamped to the padded problem shape, so
    tiny or ragged layers never trip the kernels' divisibility asserts
    — a 10-output CIFAR head runs with bm=32, not a 128-row tile.
    ``unpack=True`` selects the unpack-MXU VMEM model for the heuristic.
    """
    if any(_is_auto(v) for v in (block_m, block_n, block_kw, word_group)):
        cfg = None
        if cache_enabled():
            cfg = load_entry(kernel, {"m": m, "kw": kw, "n": n})
        if cfg is None:
            cfg = heuristic_gemm_blocks(m, kw, n, fused=fused, unpack=unpack)
        block_m = cfg.block_m if _is_auto(block_m) else block_m
        block_n = cfg.block_n if _is_auto(block_n) else block_n
        block_kw = cfg.block_kw if _is_auto(block_kw) else block_kw
        word_group = cfg.word_group if _is_auto(word_group) else word_group
    m_mult = PACK_BITS if fused else 8
    block_m = max(m_mult, min(int(block_m), _round_up(max(m, 1), m_mult)))
    if fused:
        block_m = _round_up(block_m, PACK_BITS)
    block_n = max(1, min(int(block_n), _round_up(max(n, 1), 128)))
    block_kw = max(1, min(int(block_kw), max(kw, 1)))
    return block_m, block_n, block_kw, int(word_group)


def resolve_conv_block_d(
    kernel: str, d: int, hp: int, wp: int, cw: int, kh: int, kw: int,
    ow: int, block_d, word_group,
) -> tuple[int, int]:
    """Conv sibling of :func:`resolve_gemm_blocks` (block_d only).

    No conv tuner exists yet (``tune`` speaks the GEMM wrapper
    signature), so the cache lookup here serves hand-seeded or
    future-tuner entries; ``ow`` is part of the key because it folds in
    stride — two convs differing only in stride have different window
    counts and VMEM footprints and must not share an entry.
    """
    if _is_auto(block_d) or _is_auto(word_group):
        cfg = None
        if cache_enabled():
            cfg = load_entry(
                kernel,
                {"d": d, "hp": hp, "wp": wp, "cw": cw, "kh": kh, "kw": kw,
                 "ow": ow},
            )
        if cfg is None:
            cfg = heuristic_conv_block_d(d)
        block_d = cfg.block_m if _is_auto(block_d) else block_d
        word_group = cfg.word_group if _is_auto(word_group) else word_group
    block_d = max(
        PACK_BITS, min(int(block_d), _round_up(max(d, 1), PACK_BITS))
    )
    return block_d, int(word_group)


def block_kwargs(blocks, *, conv: bool = False) -> dict:
    """Config-surface helper: a ``BitLinearConfig.blocks`` /
    ``BNNConfig.blocks`` value (``"auto"`` or a :class:`BlockConfig`)
    -> keyword arguments for the ``kernels.ops`` wrappers."""
    if _is_auto(blocks) or blocks is None:
        return {}
    if isinstance(blocks, BlockConfig):
        return blocks.conv_kwargs() if conv else blocks.gemm_kwargs()
    raise TypeError(f"blocks must be 'auto' or BlockConfig, got {blocks!r}")


__all__ = [
    "AUTO",
    "BlockConfig",
    "VMEM_BUDGET_BYTES",
    "MEGAKERNEL_KERNEL",
    "MEGAKERNEL_VMEM_BUDGET",
    "gemm_step_vmem",
    "conv_step_vmem",
    "megakernel_vmem",
    "gemm_blocks_legal",
    "heuristic_gemm_blocks",
    "heuristic_conv_block_d",
    "ResidualTile",
    "RESIDUAL_TILE_ELEMS",
    "residual_conv_tile",
    "heuristic_megakernel_block_n",
    "megakernel_shape",
    "cache_enabled",
    "cache_path",
    "save_entry",
    "load_entry",
    "default_gemm_candidates",
    "time_call",
    "rand_packed",
    "tune",
    "tune_block_n",
    "resolve_gemm_blocks",
    "resolve_conv_block_d",
    "resolve_megakernel_block_n",
    "block_kwargs",
    "megakernel_block_kwargs",
]
