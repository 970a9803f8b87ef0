"""Compiled-executor cache: one jit'd BNN forward per shape bucket.

XLA specializes executables to input shapes, so each ``(bucket, engine,
conv_impl, blocks)`` combination compiles exactly once; after warmup,
steady-state traffic is pure cache hits and the compile count equals
the number of distinct buckets warmed (asserted in
``tests/test_serve.py`` and recorded in BENCH_serving.json).

The executors run the served model's serve fn (``model=``, a
``serve.model.ServedModel``: serve fn, per-image shape and dtype,
failover ladder). The default, ``CIFAR_BNN``, is
:func:`repro.core.bnn.bnn_serve_fn` — the jit'd fused packed pipeline —
and adds nothing to the cache keys; any other model adds its name.
Warm-up inputs take the model's shape and dtype. When ``blocks="auto"``
each Pallas launch inside the traced program resolves its tiles through
the PR-3 autotune cache (``kernels/autotune.py``): a ladder warmed once
on a machine with a populated cache compiles straight to the tuned
tilings, no re-measurement in the serving path.

``engine`` accepts every :data:`repro.core.bnn.SERVE_ENGINES` value:
``"xla"``/``"xnor"`` dispatch the per-layer fused chain
(``pack_bnn_params_fused`` params), ``"megakernel"``/
``"megakernel_xla"`` dispatch one-launch-per-stage megakernel forwards
(``pack_bnn_params_megakernel`` params, DESIGN.md §8) — the bucket
ladder, cache keys and steady-state compile invariant are identical,
so a deployment flips engines by constructing the cache with the
matching packed params and engine string.

:class:`RaggedExecutorCache` is the continuous scheduler's variant
(DESIGN.md §9): it keys executors on tile-padded EXTENT classes instead
of bucket rungs — ``extent_for`` rounds a ragged batch up to the next
power of two below the sublane tile, then to tile multiples — and its
executors run ``bnn_serve_fn(..., ragged=True)`` so the megakernel FC
trunk pads only to the tile, never a ``block_n`` rung. The XLA compile
discipline is unchanged: one executable per extent class, all warmable
ahead of traffic.

Both caches accept a ``mesh=`` (a 1-D serving mesh from
``launch.mesh.make_serving_mesh``, DESIGN.md §10): executors are then
built with ``bnn_serve_fn(mesh=...)`` — weights replicated, batch
sharded over ``data`` — the cache key gains a device-count component
(``meshN``) so sharded executables never alias single-device ones, the
extent ladder scales to ``devices * extent_for(ceil(n/devices))`` so
every dispatched shape divides the mesh, and any out-of-ladder batch is
padded with bit-neutral zero rows to the next device multiple (sliced
back to exact rows) instead of crashing. The steady-state compile
invariant is unchanged: one executable per (shape class x mesh) key.

Placement contract of a meshed cache: every input of a launch already
lives where the ``shard_map`` specs (``distributed.sharding.serve_specs``)
want it, so a launch moves nothing between devices.

* The packed weights are placed on the mesh once, replicated, when the
  cache is built. ``rebuild()`` (mesh shrink, engine failover) places
  again from the unplaced tree the cache was built from (``unplaced``),
  never from the old mesh's copy.
* Each batch goes from the host straight onto its shards: every device
  receives its own rows, with no staging on one device.
* ``warmup()`` builds its input through the same placement, so the
  executable it compiles is the one the timed dispatches hit.

``ServeStats.weight_placements`` and ``sharded_puts`` count both. A
single-device cache (``mesh=None``) places nothing: weights as given,
batch through ``jnp.asarray``.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from repro.kernels.ops import RAGGED_TILE_N
from repro.serve.model import CIFAR_BNN, ServedModel
from repro.serve.stats import ServeStats

_UNSET = object()  # rebuild() sentinel: mesh=None is a meaningful override


def extent_for(n: int, *, tile: int = RAGGED_TILE_N, devices: int = 1) -> int:
    """The tile-padded extent class a ragged ``n``-row batch dispatches
    at: the next power of two while below ``tile`` (so light traffic
    compiles 1/2/4-row executables instead of padding everything to a
    full tile), then the next ``tile`` multiple. Monotone in ``n`` and
    ``extent_for(e) == e`` for every class ``e`` — the class set is
    closed under re-dispatch.

    ``devices > 1`` (mesh-sharded dispatch, DESIGN.md §10) applies the
    SAME ladder to the per-device shard and scales back up: the class is
    ``devices * extent_for(ceil(n / devices))``, so every class divides
    the mesh and each device sees a shard extent that is itself a valid
    single-device class (1/2/4 then tile multiples — full-tile classes
    land on ``tile x devices`` multiples globally). Monotonicity and
    closure under re-dispatch carry over because ``extent_for`` is
    idempotent on its own classes."""
    if n < 1:
        raise ValueError(f"batch needs >= 1 rows, got {n}")
    if devices > 1:
        return devices * extent_for(-(-n // devices), tile=tile)
    if n < tile:
        e = 1
        while e < n:
            e *= 2
        return min(e, tile)
    return -(-n // tile) * tile


def default_extents(max_rows: int, *, tile: int = RAGGED_TILE_N,
                    devices: int = 1) -> tuple[int, ...]:
    """Every extent class ``extent_for`` can produce for batches up to
    ``max_rows`` — the continuous engine's warmup set (compile count is
    ``log2(tile) + max_rows/tile``, e.g. 7 classes for tile 8, max 32).
    With ``devices > 1`` the set is the per-device-shard class set
    scaled by the device count (same cardinality bound, taken over
    ``ceil(max_rows / devices)`` shard rows)."""
    if max_rows < 1:
        raise ValueError(f"max_rows must be >= 1, got {max_rows}")
    if devices > 1:
        return tuple(
            devices * e
            for e in default_extents(-(-max_rows // devices), tile=tile)
        )
    cap = extent_for(max_rows, tile=tile)
    exts: list[int] = []
    e = 1
    while e < tile:
        if e <= cap:
            exts.append(e)
        e *= 2
    exts.extend(range(tile, cap + 1, tile))
    return tuple(exts)


def blocks_key(blocks) -> str:
    """Stable cache-key fragment for a ``blocks`` config value."""
    if isinstance(blocks, str):
        return blocks
    # kernels.autotune.BlockConfig (frozen dataclass) or anything with
    # the same fields — spell the tiling out so distinct configs never
    # collide.
    return (f"bm{blocks.block_m}-bn{blocks.block_n}"
            f"-bkw{blocks.block_kw}-wg{blocks.word_group}")


def _compile_span(built: bool, extent: int):
    """``serve.compile`` around the first call of a just-built executor
    (jit compiles on that call); nothing otherwise."""
    if built:
        return TraceAnnotation("serve.compile", extent=extent)
    return contextlib.nullcontext()


class ExecutorCache:
    """Lazy per-bucket executor map with hit/miss/compile accounting."""

    def __init__(
        self,
        packed_params: dict,
        *,
        engine: str = "xla",
        conv_impl: str = "im2col",
        blocks: object = "auto",
        mesh: object = None,
        stats: Optional[ServeStats] = None,
        model: ServedModel = CIFAR_BNN,
    ):
        from repro.distributed.sharding import mesh_devices, serve_specs

        self.model = model
        self.unplaced = packed_params
        self.packed = packed_params
        self.engine = engine
        self.conv_impl = conv_impl
        self.blocks = blocks
        self.mesh = mesh
        self.devices = mesh_devices(mesh)
        self.stats = stats if stats is not None else ServeStats()
        self._fns: dict[tuple, object] = {}
        if mesh is not None:
            p_spec, x_spec, _ = serve_specs(mesh)
            self._batch_sharding = NamedSharding(mesh, x_spec)
            self.packed = jax.device_put(packed_params,
                                         NamedSharding(mesh, p_spec))
            self.stats.on_weight_placement()

    def _mesh_key(self) -> tuple:
        """Device-count key component — present only for meshed caches,
        so single-device keys (and the stats strings tests/benchmarks
        pin) are unchanged, while a mesh-sharded executable can never
        alias a single-device one of the same bucket shape."""
        return (f"mesh{self.devices}",) if self.mesh is not None else ()

    def _model_key(self) -> tuple:
        """Model key component — absent for the default CIFAR model, so
        its keys are unchanged."""
        return () if self.model == CIFAR_BNN else (self.model.name,)

    def key(self, bucket: int) -> tuple:
        return (bucket, self.engine, self.conv_impl,
                blocks_key(self.blocks)) + self._mesh_key() + self._model_key()

    def _build(self, ragged: bool = False):
        return self.model.serve_fn(engine=self.engine,
                                   conv_impl=self.conv_impl,
                                   blocks=self.blocks, ragged=ragged,
                                   mesh=self.mesh)

    def get(self, bucket: int):
        """The compiled callable for ``bucket``; builds (and counts a
        compile) on first use of that bucket."""
        k = self.key(bucket)
        fn = self._fns.get(k)
        if fn is not None:
            self.stats.on_executor("|".join(map(str, k)), hit=True,
                                   compiled=False)
            return fn
        # One miss == one jit build == one XLA compile for this shape
        # (the bucket fixes the only varying dimension).
        fn = self._build()
        self._fns[k] = fn
        self.stats.on_executor("|".join(map(str, k)), hit=False,
                               compiled=True)
        return fn

    def run(self, images: np.ndarray) -> np.ndarray:
        """Execute the bucket-shaped batch (rows == some bucket size).

        Returns host logits ``[rows, num_classes]`` for the rows passed
        in. On a meshed cache a batch whose row count does not divide
        the device count is padded with bit-neutral zero rows up to the
        next device multiple (and the pad rows' logits sliced back off)
        rather than crashing in shard_map — the engine's ladder is
        normalized to device multiples (``buckets.mesh_buckets``), so
        this pad only fires for out-of-ladder dispatch.
        """
        n = images.shape[0]
        return self._execute(images, -(-n // self.devices) * self.devices)

    def _execute(self, images: np.ndarray, extent: int) -> np.ndarray:
        """Run ``images`` at ``extent`` rows (bit-neutral zero rows
        appended) and return the host logits of the rows passed in."""
        n = images.shape[0]
        built = self.key(extent) not in self._fns
        fn = self.get(extent)
        if extent != n:
            with TraceAnnotation("serve.assemble"):
                pad = np.zeros((extent - n,) + images.shape[1:], images.dtype)
                images = np.concatenate([np.asarray(images), pad], axis=0)
        with TraceAnnotation("serve.h2d", shards=self.devices,
                             bytes=images.nbytes):
            x = self._put(images)
        if self.mesh is not None:
            self.stats.on_sharded_put()
        with _compile_span(built, extent), TraceAnnotation("serve.launch"):
            out = fn(self.packed, x)
        if TraceAnnotation.is_enabled():
            # A profiler is recording: time the device apart from the
            # device-to-host copy. Waiting twice wakes this thread twice,
            # which costs ~3% of a v5e's backlog images/s, so only a
            # trace pays it; the copy is asked for first so that it
            # still starts when the device finishes.
            out.copy_to_host_async()
            with TraceAnnotation("serve.wait"):
                out.block_until_ready()
        with TraceAnnotation("serve.d2h"):
            return np.asarray(out)[:n]

    def _put(self, images: np.ndarray):
        """The batch where the executor reads it: on the one device, or
        each shard straight from the host onto its device of the mesh."""
        if self.mesh is None:
            return jnp.asarray(images)
        return jax.device_put(images, self._batch_sharding)

    def _ctor_kwargs(self) -> dict:
        return dict(engine=self.engine, conv_impl=self.conv_impl,
                    blocks=self.blocks, mesh=self.mesh, stats=self.stats,
                    model=self.model)

    def rebuild(self, *, packed=None, engine: Optional[str] = None,
                mesh=_UNSET):
        """A fresh cache of the same class with ``packed``/``engine``/
        ``mesh`` overridden — the failover and mesh-shrink paths
        (DESIGN.md §11).  The stats recorder is SHARED with the old
        cache, so compile/hit accounting stays continuous across a
        demotion or shrink; executables are not carried over (they are
        specialized to the old engine/mesh), and the new cache places
        the unplaced weights on its own mesh."""
        kw = self._ctor_kwargs()
        if engine is not None:
            kw["engine"] = engine
        if mesh is not _UNSET:
            kw["mesh"] = mesh
        return type(self)(self.unplaced if packed is None else packed, **kw)

    def warmup(self, buckets: Sequence[int]) -> int:
        """Compile every bucket ahead of traffic (zeros input, placed
        as a dispatch places its batch; the executable is specialized to
        shape and placement, values are irrelevant). Returns the number
        of executors built by this call."""
        built = 0
        for b in buckets:
            new = self.key(b) not in self._fns
            built += new
            fn = self.get(b)
            x = self._put(np.zeros((b,) + self.model.image_shape,
                                   self.model.dtype))
            with _compile_span(new, b):
                fn(self.packed, x).block_until_ready()
        return built

    @property
    def size(self) -> int:
        return len(self._fns)


class RaggedExecutorCache(ExecutorCache):
    """Executor cache keyed on tile-padded extent classes (DESIGN.md §9).

    The continuous scheduler assembles EXACT-row batches; ``run`` rounds
    each up to its :func:`extent_for` class, zero-pads only that far
    (per-sample independence makes pad rows bit-neutral, exactly as in
    the bucket path) and slices the real rows back out. Executors are
    built with ``bnn_serve_fn(..., ragged=True)`` so the megakernel FC
    trunk takes the masked-tail batch path — pad-to-tile instead of
    pad-to-``block_n``-rung — which is a documented no-op for the
    exact-shape XLA engines. The cache key carries a ``ragged`` marker
    so a process running both schedulers over one stats recorder never
    aliases executables across dispatch disciplines.
    """

    def __init__(self, packed_params: dict, *, tile: int = RAGGED_TILE_N,
                 **kwargs):
        super().__init__(packed_params, **kwargs)
        self.tile = int(tile)

    def _ctor_kwargs(self) -> dict:
        kw = super()._ctor_kwargs()
        kw["tile"] = self.tile
        return kw

    def key(self, extent: int) -> tuple:
        return (extent, self.engine, self.conv_impl,
                blocks_key(self.blocks), "ragged") + self._mesh_key() \
            + self._model_key()

    def _build(self):
        return super()._build(ragged=True)

    def extent_of(self, n: int) -> int:
        return extent_for(n, tile=self.tile, devices=self.devices)

    def run(self, images: np.ndarray) -> np.ndarray:
        """Execute an exact-row ragged batch at its extent class.

        Returns host logits ``[n, num_classes]`` for the REAL rows only.
        """
        return self._execute(images, self.extent_of(images.shape[0]))


__all__ = [
    "ExecutorCache",
    "RaggedExecutorCache",
    "blocks_key",
    "default_extents",
    "extent_for",
]
