"""Continuous-batching scheduler: ragged coalescing over the
variable-extent megakernel (serving engine v2, DESIGN.md §9).

The PR-4 bucket ladder pads every dispatch to a fixed rung (1/8/32/128):
pad rows burn xnor-popcount compute and full-bucket/timeout flushing
adds tail latency at awkward arrival rates. The paper's speedups come
from never wasting work on bits that don't exist; this scheduler
applies the same discipline to rows. On each ``step()`` it admits
whatever requests are queued — up to a row budget ``max_rows`` — and
concatenates their REAL rows into one contiguous ragged batch with
per-request row offsets (the existing ``Segment`` bookkeeping),
dispatching one launch whose batch extent is a tile-padded EXTENT CLASS
(``executor.extent_for``: powers of two below the sublane tile, then
tile multiples), never a bucket rung. Inside the megakernel the extent
is handled by the masked-tail batch path (``ragged=True`` through
``bnn_serve_fn``): N pads only to ``RAGGED_TILE_N``, and a tail grid
step zeroes its overhang against the traced ``n_real`` — the
dynamic-extent discipline whose precedent is
``popcount.accum_popcount_km_dyn``'s traced trip counts.

Policy knobs beyond the ladder's:

* **admission control** — ``max_queue_rows`` bounds queued rows;
  ``submit`` past the bound raises :class:`QueueFull` (counted under
  ``requests.rejected`` in the snapshot). An open-loop overload then
  sheds load at the front door instead of growing an unbounded queue
  whose every resident blows the SLO.
* **SLO-aware max-wait** — with ``slo_s`` set, the coalescing wait for
  a non-full batch shrinks as the head-of-line request's latency budget
  is consumed: the batcher keeps an EWMA of observed per-row service
  time and waits at most ``slo_s * slo_headroom - est_service(pending)``
  (never more than ``max_wait_s``). Light traffic still coalesces;
  traffic near the SLO edge dispatches immediately.

Bit-identity is inherited, not re-proven: ragged pad rows are zero
images, per-sample independence makes them bit-neutral (the §7
bucketing argument), and the masked-tail kernel path is asserted
bit-identical to the exact-N oracle in ``tests/test_megakernel.py`` —
so every request served here yields logits bit-identical to its
exact-shape execution (asserted across engine x conv_impl in
``tests/test_serve.py`` / ``tests/test_properties.py``).
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve.engine import ServingEngine
from repro.serve.executor import (
    RaggedExecutorCache,
    default_extents,
    extent_for,
)
from repro.serve.model import CIFAR_BNN, ServedModel
from repro.serve.queue import MicroBatcher

DEFAULT_MAX_ROWS = 32  # per-dispatch row budget (the ladder's top rung / 4)


class QueueFull(RuntimeError):
    """Admission control rejected a submit: queued rows would exceed
    ``max_queue_rows``. The request never entered the queue; the caller
    retries later or sheds the work.

    ``retry_after_s`` is the batcher's estimate of how long until the
    overflow clears — the service-time EWMA applied to the rows past
    the bound (falling back to the coalescing wait before the first
    observation lands). A well-behaved client backs off at least this
    long instead of hammering the front door."""

    def __init__(self, msg: str, *, retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class ContinuousBatcher(MicroBatcher):
    """Ragged coalescer: FIFO admission up to a row budget, no ladder.

    Reuses the MicroBatcher's cursor/segment machinery (``_take`` and
    the split bookkeeping are scheduler-agnostic) but every batch it
    emits carries ``bucket == rows`` — exact rows out; the executor
    cache, not the queue, decides the padded extent class. ``poll``
    keeps the ladder's two flush triggers with new meanings:

    * **full** — pending rows reach ``max_rows``: dispatch a
      budget-sized batch immediately.
    * **max_wait** — the head-of-line request has waited out the
      CURRENT wait bound: dispatch everything pending (<= ``max_rows``)
      as one ragged batch. The bound is ``max_wait_s``, shrunk by the
      SLO budget when ``slo_s`` is set (see :meth:`current_wait`).
    """

    def __init__(
        self,
        *,
        max_rows: int = DEFAULT_MAX_ROWS,
        max_wait_s: float = 0.002,
        max_queue_rows: Optional[int] = None,
        slo_s: Optional[float] = None,
        slo_headroom: float = 0.5,
        clock: Callable[[], float] = time.monotonic,
    ):
        # The parent's ladder degenerates to the single budget rung —
        # max_bucket doubles as the per-dispatch row budget.
        super().__init__([int(max_rows)], max_wait_s=max_wait_s, clock=clock)
        self.max_rows = int(max_rows)
        if max_queue_rows is not None and max_queue_rows < self.max_rows:
            raise ValueError(
                f"max_queue_rows {max_queue_rows} < max_rows "
                f"{self.max_rows}: admission would reject batches the "
                f"budget could serve"
            )
        self.max_queue_rows = max_queue_rows
        self.slo_s = slo_s
        self.slo_headroom = float(slo_headroom)
        # EWMA of observed seconds-per-row across dispatches; None until
        # the first service observation lands.
        self._row_s: Optional[float] = None

    # -- producer side -----------------------------------------------------
    def submit(self, images: np.ndarray) -> int:
        images = np.asarray(images)
        n = images.shape[0] if images.ndim >= 1 else 0
        if (
            self.max_queue_rows is not None
            and self._pending_rows + max(n, 1) > self.max_queue_rows
        ):
            overflow = self._pending_rows + max(n, 1) - self.max_queue_rows
            hint = self.est_service_s(overflow)
            raise QueueFull(
                f"{self._pending_rows} rows queued + {n} > "
                f"max_queue_rows {self.max_queue_rows}",
                retry_after_s=hint if hint > 0.0 else self.max_wait_s,
            )
        return super().submit(images)

    # -- service model -----------------------------------------------------
    def note_service(self, rows: int, seconds: float) -> None:
        """Fold one dispatch observation into the per-row EWMA (the
        engine calls this after every launch; 0.3 smoothing keeps ~3-4
        dispatches of memory, enough to track warmup -> steady state)."""
        if rows < 1 or seconds <= 0.0:
            return
        per_row = seconds / rows
        self._row_s = (
            per_row if self._row_s is None
            else 0.7 * self._row_s + 0.3 * per_row
        )

    def est_service_s(self, rows: int) -> float:
        """Estimated service time of an ``rows``-row dispatch (0.0 until
        the first observation — optimistic, so cold starts coalesce)."""
        if self._row_s is None:
            return 0.0
        return self._row_s * max(rows, 1)

    def current_wait(self) -> float:
        """The coalescing bound ``poll`` holds a non-full batch to.

        Without an SLO: the static ``max_wait_s``. With one: the
        remaining latency budget of the pending work — ``slo_s *
        slo_headroom`` (headroom < 1 leaves room for queueing noise and
        the next arrival burst) minus the estimated service time of
        dispatching everything pending now — clipped to
        ``[0, max_wait_s]``. A hot queue or a slow model drives the
        bound to zero and the batch leaves immediately.
        """
        if self.slo_s is None:
            return self.max_wait_s
        budget = self.slo_s * self.slo_headroom
        budget -= self.est_service_s(min(self._pending_rows, self.max_rows))
        return max(0.0, min(self.max_wait_s, budget))

    # -- consumer side -----------------------------------------------------
    def poll(self) -> list:
        out = []
        while self._pending_rows >= self.max_rows:
            out.append(self._take(self.max_rows, self.max_rows, "full"))
        if self._pending_rows and self.oldest_wait() >= self.current_wait():
            rows = self._pending_rows
            out.append(self._take(rows, rows, "max_wait"))
        return out

    def drain(self) -> list:
        out = []
        while self._pending_rows >= self.max_rows:
            out.append(self._take(self.max_rows, self.max_rows, "drain"))
        if self._pending_rows:
            rows = self._pending_rows
            out.append(self._take(rows, rows, "drain"))
        return out


class ContinuousServingEngine(ServingEngine):
    """Serving engine v2: the continuous batcher over the ragged
    executor cache — same ``submit/step/drain/take`` surface (plus
    ``cancel``) as :class:`~repro.serve.engine.ServingEngine`, same
    bit-identity contract, different dispatch discipline.

    ``packed_params``/``engine``/``conv_impl``/``blocks`` mean exactly
    what they do for the bucket engine; ``max_rows`` bounds one
    dispatch, ``max_queue_rows`` bounds admission (:class:`QueueFull`
    on overflow), ``slo_s`` both arms the SLO-aware wait and makes the
    snapshot's goodput figure meaningful. ``warmup`` compiles every
    extent class ``default_extents(max_rows)`` instead of a ladder.

    ``mesh`` (DESIGN.md §10) shards every dispatch data-parallel over a
    1-D serving mesh: the extent ladder becomes mesh-multiple classes
    (``extent_for(..., devices=n)`` — closed under re-dispatch exactly
    like the single-device ladder) and the ragged executor pads a
    coalesced batch bit-neutrally up to its mesh-divisible extent, so a
    3-real-row batch on 8 devices dispatches at extent 8 and hands back
    exactly 3 rows. Per-request logits remain bit-identical to
    exact-shape single-device execution.

    ``model`` (``serve.model.ServedModel``, default the CIFAR BNN) is
    the network served: its serve fn, request shape and dtype, and
    failover ladder — e.g. ``core.bireal.served_model()`` with
    ``pack_bireal_params`` params and uint8 ``[n, 224, 224, 3]``
    requests.
    """

    def __init__(
        self,
        packed_params: dict,
        *,
        engine: str = "xla",
        conv_impl: str = "im2col",
        blocks: object = "auto",
        max_rows: int = DEFAULT_MAX_ROWS,
        max_wait_s: float = 0.002,
        max_queue_rows: Optional[int] = None,
        slo_s: Optional[float] = None,
        slo_headroom: float = 0.5,
        mesh: object = None,
        deadline_s: Optional[float] = None,
        retry=None,
        fallback=None,
        faults=None,
        heartbeat_timeout_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
        model: ServedModel = CIFAR_BNN,
    ):
        # Deliberately NOT calling super().__init__: the base wires a
        # bucket MicroBatcher + bucket ExecutorCache; everything else
        # (submit validation, retry/deadline pump, _run scatter loop,
        # take/cancel) is inherited behavior over the attributes set
        # here (resilience state via the shared _init_resilience).
        from repro.serve.stats import ServeStats

        self.stats = ServeStats(scheduler="continuous", slo_s=slo_s)
        self.clock = clock
        self.batcher = ContinuousBatcher(
            max_rows=max_rows, max_wait_s=max_wait_s,
            max_queue_rows=max_queue_rows, slo_s=slo_s,
            slo_headroom=slo_headroom, clock=clock,
        )
        self.executors = RaggedExecutorCache(
            packed_params, engine=engine, conv_impl=conv_impl,
            blocks=blocks, mesh=mesh, stats=self.stats,
            model=model,
        )
        self.extents = default_extents(
            max_rows, tile=self.executors.tile,
            devices=self.executors.devices,
        )
        self._partial = {}
        self._filled = {}
        self.results = {}
        self._init_resilience(deadline_s, retry, fallback, faults,
                              heartbeat_timeout_s)

    def _warm_shapes(self):
        """Tile-padded extent classes instead of bucket rungs — warmed
        by both ``warmup`` and ``prewarm_fallback``."""
        return self.extents

    def submit(self, images: np.ndarray, *,
               deadline_s: Optional[float] = None) -> int:
        """Enqueue one request; raises :class:`QueueFull` (carrying a
        ``retry_after_s`` backoff hint, and counting the rejection)
        when admission control turns it away."""
        try:
            return super().submit(images, deadline_s=deadline_s)
        except QueueFull:
            n = np.asarray(images).shape[0]
            self.stats.on_reject(n)
            raise

    def _dispatch(self, batch) -> tuple[np.ndarray, int]:
        """Ragged dispatch: exact rows assembled, extent-class padding
        applied inside the executor; the service wall feeds the
        SLO-aware wait's EWMA and the stats record the extent actually
        run (pad waste = extent - real rows). Runs through the base
        engine's fault plan + NaN guard (`_execute_rows`); a faulted
        dispatch contributes no service observation."""
        with TraceAnnotation("serve.assemble"):
            x = batch.assemble(self.batcher.requests)
        extent = self._extent(batch)
        t0 = self.clock()
        logits = self._execute_rows(x)
        self.batcher.note_service(extent, self.clock() - t0)
        return logits, extent

    def _extent(self, batch) -> int:
        return self.executors.extent_of(batch.rows)

    def _on_remesh(self) -> None:
        # The extent ladder is device-multiple-scaled; after an elastic
        # shrink it must be recomputed at the survivor count so warmup
        # compiles the classes extent_of will actually produce.
        self.extents = default_extents(
            self.batcher.max_rows, tile=self.executors.tile,
            devices=self.executors.devices,
        )


__all__ = [
    "ContinuousBatcher",
    "ContinuousServingEngine",
    "QueueFull",
    "DEFAULT_MAX_ROWS",
    "extent_for",
]
