"""Serving engine: padding neutrality (the bucketing correctness
claim), deterministic micro-batcher behavior under a fake clock,
executor-cache accounting, and end-to-end request/result integrity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.bnn import (
    bnn_apply_fused,
    bnn_serve_fn,
    init_bnn_params,
    pack_bnn_params_fused,
)
from repro.serve import (
    ContinuousBatcher,
    ContinuousServingEngine,
    MicroBatcher,
    QueueFull,
    ServingEngine,
    bucket_for,
    default_extents,
    extent_for,
    normalize_buckets,
    pad_to_bucket,
)
from repro.serve.executor import ExecutorCache, blocks_key

KEY = jax.random.PRNGKey(99)


class FakeClock:
    """Deterministic clock for queue tests: advances only on demand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def fused_params():
    return pack_bnn_params_fused(init_bnn_params(KEY))


@pytest.fixture(scope="module")
def images():
    return jax.random.normal(jax.random.fold_in(KEY, 1), (8, 32, 32, 3))


# ---------------------------------------------------------------------------
# Bucket helpers
# ---------------------------------------------------------------------------

def test_bucket_ladder_helpers():
    assert normalize_buckets([32, 1, 8, 8]) == (1, 8, 32)
    assert bucket_for(1, (1, 8, 32)) == 1
    assert bucket_for(2, (1, 8, 32)) == 8
    assert bucket_for(32, (1, 8, 32)) == 32
    with pytest.raises(ValueError):
        bucket_for(33, (1, 8, 32))
    with pytest.raises(ValueError):
        normalize_buckets([])


def test_pad_to_bucket_appends_zero_rows():
    x = np.ones((3, 2, 2, 1), np.float32)
    p = pad_to_bucket(x, 8)
    assert p.shape == (8, 2, 2, 1)
    np.testing.assert_array_equal(p[:3], x)
    assert not p[3:].any()
    assert pad_to_bucket(x, 3) is x  # exact fit: no copy
    with pytest.raises(ValueError):
        pad_to_bucket(x, 2)


# ---------------------------------------------------------------------------
# Padding neutrality — the core correctness claim of shape bucketing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["xla", "xnor"])
@pytest.mark.parametrize("conv_impl", ["im2col", "direct"])
def test_padding_neutral_logits(fused_params, images, engine, conv_impl):
    """For EVERY engine x conv_impl pair: a request padded up to a
    larger bucket yields bit-identical logits on the real rows vs
    exact-shape execution. (The forward is per-sample independent, so
    the zero padding rows cannot perturb the real rows.)"""
    # interpret-mode Pallas is python-speed: keep the xnor pairs tiny
    n, bucket = (1, 2) if engine == "xnor" else (3, 8)
    imgs = np.asarray(images[:n])
    exact = np.asarray(
        bnn_apply_fused(fused_params, jnp.asarray(imgs), engine=engine,
                        conv_impl=conv_impl)
    )
    padded_out = np.asarray(
        bnn_apply_fused(
            fused_params, jnp.asarray(pad_to_bucket(imgs, bucket)),
            engine=engine, conv_impl=conv_impl,
        )
    )
    np.testing.assert_array_equal(padded_out[:n], exact)


def test_padding_rows_do_not_depend_on_real_rows(fused_params, images):
    """Dual check: the real rows' logits are identical no matter WHAT
    shares the batch with them (zeros or other live images)."""
    a = np.asarray(images[:2])
    batch_zeros = pad_to_bucket(a, 4)
    batch_other = np.concatenate([a, np.asarray(images[2:4])], axis=0)
    za = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(batch_zeros)))
    zb = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(batch_other)))
    np.testing.assert_array_equal(za[:2], zb[:2])


# ---------------------------------------------------------------------------
# Micro-batcher under a fake clock
# ---------------------------------------------------------------------------

def _rows(batches):
    """Flatten emitted batches into (rid, request_row) pairs, in order."""
    out = []
    for b in batches:
        for s in b.segments:
            out.extend((s.rid, s.offset + i) for i in range(s.length))
    return out


def test_max_wait_flush_with_fake_clock():
    clk = FakeClock()
    mb = MicroBatcher((1, 4, 8), max_wait_s=0.5, clock=clk)
    mb.submit(np.zeros((2, 1, 1, 1)))
    assert mb.poll() == []                      # young: no flush
    clk.advance(0.49)
    assert mb.poll() == []                      # still inside max_wait
    clk.advance(0.02)
    (batch,) = mb.poll()
    assert batch.reason == "max_wait"
    assert batch.bucket == 4 and batch.rows == 2
    assert mb.pending_rows == 0


def test_full_bucket_flushes_immediately():
    clk = FakeClock()
    mb = MicroBatcher((1, 4), max_wait_s=10.0, clock=clk)
    mb.submit(np.zeros((3, 1, 1, 1)))
    mb.submit(np.zeros((3, 1, 1, 1)))
    (batch,) = mb.poll()                        # 6 rows >= max bucket 4
    assert batch.reason == "full"
    assert batch.bucket == 4 and batch.rows == 4
    assert mb.pending_rows == 2                 # split remainder queued

    clk.advance(11.0)
    (tail,) = mb.poll()
    assert tail.reason == "max_wait" and tail.rows == 2


def test_partial_batch_flush_on_drain():
    clk = FakeClock()
    mb = MicroBatcher((1, 4, 8), max_wait_s=10.0, clock=clk)
    mb.submit(np.zeros((1, 1, 1, 1)))
    mb.submit(np.zeros((2, 1, 1, 1)))
    assert mb.poll() == []                      # young + not full
    (batch,) = mb.drain()
    assert batch.reason == "drain"
    assert batch.bucket == 4 and batch.rows == 3
    assert mb.pending_rows == 0 and mb.drain() == []


def test_fifo_order_and_request_splitting():
    clk = FakeClock()
    mb = MicroBatcher((2, 4), max_wait_s=0.0, clock=clk)
    r0 = mb.submit(np.zeros((3, 1, 1, 1)))
    r1 = mb.submit(np.zeros((3, 1, 1, 1)))
    batches = mb.poll() + mb.drain()
    rows = _rows(batches)
    # every row exactly once, FIFO across and within requests
    assert rows == [(r0, 0), (r0, 1), (r0, 2), (r1, 0), (r1, 1), (r1, 2)]
    # r0 was split across the first full batch and the next one
    assert batches[0].rows == 4 and {s.rid for s in batches[0].segments} == {r0, r1}


def test_submit_rejects_mismatched_row_shape():
    """A bad request must bounce at submit(), not poison the batch its
    rows would have been coalesced into."""
    mb = MicroBatcher((4,), max_wait_s=0.0, clock=FakeClock())
    mb.submit(np.zeros((2, 32, 32, 3), np.float32))
    with pytest.raises(ValueError, match="row shape"):
        mb.submit(np.zeros((1, 28, 28, 3), np.float32))
    with pytest.raises(ValueError):
        mb.submit(np.zeros((0, 32, 32, 3), np.float32))
    (batch,) = mb.drain()                       # queue still healthy
    assert batch.rows == 2


def test_batch_assemble_pads_and_orders():
    clk = FakeClock()
    mb = MicroBatcher((4,), max_wait_s=0.0, clock=clk)
    a = np.arange(2 * 4, dtype=np.float32).reshape(2, 2, 2, 1)
    b = 100 + np.arange(4, dtype=np.float32).reshape(1, 2, 2, 1)
    mb.submit(a)
    mb.submit(b)
    (batch,) = mb.drain()
    x = batch.assemble(mb.requests)
    assert x.shape == (4, 2, 2, 1)
    np.testing.assert_array_equal(x[:2], a)
    np.testing.assert_array_equal(x[2:3], b)
    assert not x[3:].any()                      # zero padding rows


# ---------------------------------------------------------------------------
# Executor cache accounting
# ---------------------------------------------------------------------------

def test_executor_cache_hit_miss_and_compile_counts(fused_params):
    cache = ExecutorCache(fused_params, engine="xla")
    warmed = cache.warmup((1, 4))
    assert warmed == 2
    assert cache.stats.executor_compiles == 2
    assert cache.stats.executor_misses == 2
    # steady state: only hits, no new compiles
    for _ in range(3):
        cache.get(1)
        cache.get(4)
    assert cache.stats.executor_compiles == 2
    assert cache.stats.executor_hits >= 6
    assert cache.size == 2
    # a novel bucket is a miss + one compile
    cache.get(8)
    assert cache.stats.executor_compiles == 3
    assert cache.stats.executor_keys == [
        "1|xla|im2col|auto", "4|xla|im2col|auto", "8|xla|im2col|auto"
    ]


def test_blocks_key_distinguishes_configs():
    from repro.kernels.autotune import BlockConfig

    assert blocks_key("auto") == "auto"
    k1 = blocks_key(BlockConfig(128, 256, 16, 8))
    k2 = blocks_key(BlockConfig(128, 256, 32, 8))
    assert k1 != k2 and "bm128" in k1


def test_serving_tuning_cache_roundtrip(fused_params, tmp_path, monkeypatch):
    """tune_serving_blocks persists its winner in the autotune cache;
    load_serving_blocks serves it back (and falls back to AUTO for
    unknown configurations)."""
    from repro.kernels.autotune import BlockConfig
    from repro.serve import load_serving_blocks, tune_serving_blocks

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    cfg = BlockConfig(block_m=64, block_n=128, block_kw=4, word_group=4)
    timings: dict = {}
    best = tune_serving_blocks(
        fused_params, 1, engine="xla", candidates=[cfg], repeats=1,
        timings=timings,
    )
    assert best == cfg and timings[cfg] > 0
    assert load_serving_blocks("xla", "im2col", 1) == cfg
    # unknown bucket / engine: no entry -> AUTO fallback
    assert load_serving_blocks("xla", "im2col", 64) == "auto"
    assert load_serving_blocks("xnor", "im2col", 1) == "auto"


# ---------------------------------------------------------------------------
# End-to-end engine
# ---------------------------------------------------------------------------

def test_engine_serves_ragged_requests_bit_identical(fused_params, images):
    clk = FakeClock()
    eng = ServingEngine(fused_params, engine="xla", buckets=(1, 4, 8),
                        max_wait_s=0.5, clock=clk)
    eng.warmup()
    imgs = np.asarray(images)
    requests = {eng.submit(imgs[:3]): imgs[:3]}
    eng.step()
    requests[eng.submit(imgs[3:4])] = imgs[3:4]
    clk.advance(1.0)                            # age out -> max_wait flush
    eng.step()
    requests[eng.submit(imgs[4:8])] = imgs[4:8]
    eng.drain()

    for rid, x in requests.items():
        got = eng.take(rid)
        want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(x)))
        assert got is not None
        np.testing.assert_array_equal(got, want)
    snap = eng.snapshot()
    assert snap["requests"]["completed"] == 3
    assert snap["requests"]["images_completed"] == 8
    assert snap["batches"]["real_rows"] == 8
    # warmup compiled the whole ladder; traffic added no compiles
    assert snap["executors"]["compiles"] == 3


def test_engine_reassembles_request_larger_than_max_bucket(fused_params,
                                                           images):
    """A request exceeding the largest bucket is split across batches
    and its logits reassembled in request-row order."""
    clk = FakeClock()
    eng = ServingEngine(fused_params, engine="xla", buckets=(1, 4),
                        max_wait_s=10.0, clock=clk)
    eng.warmup()
    imgs = np.asarray(images[:6])               # 6 > max bucket 4
    rid = eng.submit(imgs)
    eng.step()                                  # full 4-row batch
    assert eng.take(rid) is None                # tail still pending
    eng.drain()
    got = eng.take(rid)
    want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(imgs)))
    np.testing.assert_array_equal(got, want)


def test_engine_rejects_non_image_rows(fused_params):
    """The engine validates the model's fixed image shape at submit —
    even for the FIRST request (the queue's generic consistency check
    alone would pin itself to whatever arrives first)."""
    eng = ServingEngine(fused_params, engine="xla", buckets=(4,),
                        max_wait_s=10.0, clock=FakeClock())
    with pytest.raises(ValueError, match="32, 32, 3"):
        eng.submit(np.zeros((2, 16, 16, 3), np.float32))
    rid = eng.submit(np.zeros((1, 32, 32, 3), np.float32))  # still healthy
    eng.drain()
    assert eng.take(rid) is not None


def test_engine_latency_measured_on_injected_clock(fused_params):
    clk = FakeClock()
    eng = ServingEngine(fused_params, engine="xla", buckets=(4,),
                        max_wait_s=10.0, clock=clk)
    eng.warmup()
    eng.submit(np.zeros((2, 32, 32, 3), np.float32))
    clk.advance(3.0)
    eng.drain()
    snap = eng.snapshot()
    assert snap["latency_s"]["count"] == 1
    assert snap["latency_s"]["p50"] == pytest.approx(3.0)


def test_serve_fn_matches_apply_fused(fused_params, images):
    fn = bnn_serve_fn(engine="xla")
    got = np.asarray(fn(fused_params, images[:2]))
    want = np.asarray(bnn_apply_fused(fused_params, images[:2]))
    np.testing.assert_array_equal(got, want)


def test_serve_fn_rejects_unknown_engine():
    with pytest.raises(ValueError, match="unknown serving engine"):
        bnn_serve_fn(engine="warp-drive")


# ---------------------------------------------------------------------------
# Megakernel engine (ISSUE 5): the bucket ladder dispatches
# one-launch-per-stage executors
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mega_params():
    from repro.core.bnn import pack_bnn_params_megakernel

    return pack_bnn_params_megakernel(init_bnn_params(KEY))


@pytest.mark.parametrize("engine", ["megakernel_xla", "megakernel"])
def test_padding_neutral_logits_megakernel(mega_params, fused_params,
                                           images, engine):
    """Bucket padding stays bit-neutral under the megakernel engines,
    and the padded logits still equal the FUSED chain's (the serving
    cache may mix engines across deployments without drift)."""
    from repro.core.bnn import bnn_apply_megakernel

    n, bucket = (1, 2) if engine == "megakernel" else (3, 8)
    imgs = np.asarray(images[:n])
    inner = "xnor" if engine == "megakernel" else "xla"
    exact = np.asarray(
        bnn_apply_megakernel(mega_params, jnp.asarray(imgs), engine=inner)
    )
    padded_out = np.asarray(
        bnn_apply_megakernel(
            mega_params, jnp.asarray(pad_to_bucket(imgs, bucket)),
            engine=inner,
        )
    )
    np.testing.assert_array_equal(padded_out[:n], exact)
    want = np.asarray(
        bnn_apply_fused(fused_params, jnp.asarray(imgs), engine="xla")
    )
    np.testing.assert_array_equal(exact, want)


def test_engine_serves_megakernel_requests_bit_identical(mega_params,
                                                         images):
    """End-to-end ServingEngine on engine="megakernel_xla": ragged
    requests through the bucket ladder come back bit-identical to
    exact-shape megakernel execution, steady state compiles == buckets."""
    from repro.core.bnn import bnn_apply_megakernel

    clk = FakeClock()
    eng = ServingEngine(mega_params, engine="megakernel_xla",
                        buckets=(1, 4), max_wait_s=0.0, clock=clk)
    warmed = eng.warmup()
    imgs = np.asarray(images)
    requests = {}
    for sl in (slice(0, 3), slice(3, 4), slice(4, 8)):
        requests[eng.submit(imgs[sl])] = imgs[sl]
        eng.step()
    eng.drain()
    for rid, x in requests.items():
        got = eng.take(rid)
        want = np.asarray(
            bnn_apply_megakernel(mega_params, jnp.asarray(x), engine="xla")
        )
        np.testing.assert_array_equal(got, want)
    snap = eng.snapshot()
    assert snap["executors"]["compiles"] == warmed == 2


# ---------------------------------------------------------------------------
# Cancellation (ISSUE 6 satellite): forget() retires pending cursors
# ---------------------------------------------------------------------------

def test_forget_split_request_retires_pending_cursor():
    """Regression: cancelling a request whose tail is still queued must
    retire its (rid, offset) cursor too — the pre-fix code left an
    orphan cursor whose ghost segment poisoned the next batch."""
    clk = FakeClock()
    mb = MicroBatcher((2,), max_wait_s=10.0, clock=clk)
    r0 = mb.submit(np.zeros((3, 1, 1, 1), np.float32))
    (head,) = mb.poll()                  # full 2-row slice of r0 leaves
    assert [s.rid for s in head.segments] == [r0]
    assert mb.pending_rows == 1          # r0's tail at the queue head
    assert mb.forget(r0) is not None
    assert mb.pending_rows == 0          # cursor retired with the request
    r1 = mb.submit(np.ones((2, 1, 1, 1), np.float32))
    (nxt,) = mb.poll()
    assert [s.rid for s in nxt.segments] == [r1]   # no ghost segment
    np.testing.assert_array_equal(
        nxt.assemble(mb.requests), np.ones((2, 1, 1, 1), np.float32)
    )


def test_batch_assemble_zeroes_cancelled_batchmate_rows():
    """A request cancelled between batching and assembly contributes
    zero rows in place: batchmates' batch_row offsets stay honest."""
    clk = FakeClock()
    mb = MicroBatcher((4,), max_wait_s=0.0, clock=clk)
    a = np.ones((2, 2, 2, 1), np.float32)
    b = 2 * np.ones((1, 2, 2, 1), np.float32)
    ra = mb.submit(a)
    rb = mb.submit(b)
    (batch,) = mb.drain()
    mb.forget(ra)
    x = batch.assemble(mb.requests)
    assert not x[:2].any()               # ghost rows zeroed in place
    np.testing.assert_array_equal(x[2:3], b)
    mb.forget(rb)
    with pytest.raises(ValueError, match="cancelled"):
        batch.assemble(mb.requests)      # nothing left to assemble


def test_engine_cancel_after_split_keeps_batchmates_intact(fused_params,
                                                           images):
    """Cancel a split request between the full flush and the tail flush:
    the tail's cursor disappears and later requests serve normally."""
    clk = FakeClock()
    eng = ServingEngine(fused_params, engine="xla", buckets=(1, 4),
                        max_wait_s=10.0, clock=clk)
    eng.warmup()
    imgs = np.asarray(images)
    big = eng.submit(imgs[:6])           # splits: 4 dispatched, 2 queued
    eng.step()
    assert eng.cancel(big)
    small = eng.submit(imgs[6:8])
    done = eng.drain()
    assert small in done and big not in done
    want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(imgs[6:8])))
    np.testing.assert_array_equal(eng.take(small), want)
    assert eng.take(big) is None


def test_engine_cancel_between_poll_and_run_drops_only_that_request(
        fused_params, images):
    """Rows of a cancelled request already inside an assembled batch
    compute as zero ghosts and are dropped at scatter; batchmates'
    logits stay bit-identical to their exact-shape forward."""
    clk = FakeClock()
    eng = ServingEngine(fused_params, engine="xla", buckets=(4,),
                        max_wait_s=0.0, clock=clk)
    eng.warmup()
    imgs = np.asarray(images)
    ra = eng.submit(imgs[:2])
    rb = eng.submit(imgs[2:3])
    batches = eng.batcher.poll()         # batched but not yet run
    assert eng.cancel(ra)
    done = eng._run(batches)
    assert done == [rb]
    want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(imgs[2:3])))
    np.testing.assert_array_equal(eng.take(rb), want)
    assert eng.take(ra) is None


def test_engine_skips_batch_when_every_request_cancelled(fused_params):
    clk = FakeClock()
    eng = ServingEngine(fused_params, engine="xla", buckets=(4,),
                        max_wait_s=0.0, clock=clk)
    eng.warmup()
    rid = eng.submit(np.zeros((2, 32, 32, 3), np.float32))
    batches = eng.batcher.poll()
    assert eng.cancel(rid)
    assert eng._run(batches) == []       # skipped entirely, no dispatch
    assert eng.snapshot()["batches"]["dispatched"] == 0


# ---------------------------------------------------------------------------
# Continuous scheduler (ISSUE 6): ragged coalescing over extent classes
# ---------------------------------------------------------------------------

def test_extent_class_helpers():
    assert [extent_for(n) for n in (1, 2, 3, 5, 8, 9, 16, 17, 25, 32)] == \
        [1, 2, 4, 8, 8, 16, 16, 24, 32, 32]
    assert default_extents(32) == (1, 2, 4, 8, 16, 24, 32)
    assert default_extents(8) == (1, 2, 4, 8)
    assert default_extents(1) == (1,)
    for e in default_extents(32):
        assert extent_for(e) == e        # classes closed under re-dispatch
    with pytest.raises(ValueError):
        extent_for(0)
    with pytest.raises(ValueError):
        default_extents(0)


def test_continuous_batcher_full_and_ragged_flush():
    clk = FakeClock()
    cb = ContinuousBatcher(max_rows=8, max_wait_s=0.5, clock=clk)
    cb.submit(np.zeros((5, 1, 1, 1), np.float32))
    assert cb.poll() == []               # young, below budget: coalesce
    cb.submit(np.zeros((6, 1, 1, 1), np.float32))
    (full,) = cb.poll()                  # 11 pending rows >= budget 8
    assert full.reason == "full" and full.rows == full.bucket == 8
    assert cb.pending_rows == 3
    clk.advance(1.0)
    (ragged,) = cb.poll()                # aged out: EXACT rows, no rung
    assert ragged.reason == "max_wait"
    assert ragged.rows == ragged.bucket == 3


def test_continuous_admission_control():
    clk = FakeClock()
    cb = ContinuousBatcher(max_rows=4, max_queue_rows=6, clock=clk)
    cb.submit(np.zeros((4, 1, 1, 1), np.float32))
    cb.submit(np.zeros((2, 1, 1, 1), np.float32))
    with pytest.raises(QueueFull):
        cb.submit(np.zeros((1, 1, 1, 1), np.float32))
    cb.poll()                            # a dispatch frees queue budget
    cb.submit(np.zeros((1, 1, 1, 1), np.float32))
    with pytest.raises(ValueError, match="max_queue_rows"):
        ContinuousBatcher(max_rows=8, max_queue_rows=4)


def test_continuous_service_ewma():
    cb = ContinuousBatcher(max_rows=8, clock=FakeClock())
    assert cb.est_service_s(8) == 0.0    # optimistic before any data
    cb.note_service(8, 0.8)              # 0.1 s/row
    cb.note_service(8, 1.6)              # 0.2 s/row folds in at 0.3
    assert cb.est_service_s(1) == pytest.approx(0.7 * 0.1 + 0.3 * 0.2)
    cb.note_service(0, 1.0)              # degenerate observations ignored
    cb.note_service(8, 0.0)
    assert cb.est_service_s(1) == pytest.approx(0.13)


def test_continuous_slo_aware_wait_shrinks_with_load():
    clk = FakeClock()
    cb = ContinuousBatcher(max_rows=32, max_wait_s=1.0, slo_s=2.0,
                           slo_headroom=0.5, clock=clk)
    assert cb.current_wait() == 1.0      # no service data: static bound
    cb.note_service(8, 0.8)              # 0.1 s/row observed
    cb.submit(np.zeros((4, 1, 1, 1), np.float32))
    # budget 2.0*0.5 minus est service of 4 pending rows = 0.6s
    assert cb.current_wait() == pytest.approx(0.6)
    cb.submit(np.zeros((8, 1, 1, 1), np.float32))
    # 12 pending rows: est service 1.2s exceeds the budget -> no wait
    assert cb.current_wait() == 0.0
    (b,) = cb.poll()
    assert b.reason == "max_wait" and b.rows == 12


@pytest.mark.parametrize("engine", ["xla", "xnor"])
@pytest.mark.parametrize("conv_impl", ["im2col", "direct"])
def test_continuous_engine_bit_identical(fused_params, images, engine,
                                         conv_impl):
    """The v2 engine's contract (DESIGN.md §9): every request's logits
    are bit-identical to its exact-shape forward, for every engine x
    conv_impl pair — extent padding is as neutral as rung padding."""
    clk = FakeClock()
    if engine == "xnor":                 # interpret Pallas is python-speed
        max_rows, slices = 2, (slice(0, 1), slice(1, 3))
    else:
        max_rows, slices = 4, (slice(0, 3), slice(3, 4), slice(4, 8))
    eng = ContinuousServingEngine(fused_params, engine=engine,
                                  conv_impl=conv_impl, max_rows=max_rows,
                                  max_wait_s=0.0, clock=clk)
    imgs = np.asarray(images)
    requests = {}
    for sl in slices:
        requests[eng.submit(imgs[sl])] = imgs[sl]
        eng.step()
    eng.drain()
    for rid, x in requests.items():
        got = eng.take(rid)
        want = np.asarray(
            bnn_apply_fused(fused_params, jnp.asarray(x), engine=engine,
                            conv_impl=conv_impl)
        )
        assert got is not None
        np.testing.assert_array_equal(got, want)


def test_continuous_engine_extent_accounting(fused_params):
    clk = FakeClock()
    eng = ContinuousServingEngine(fused_params, engine="xla", max_rows=8,
                                  max_wait_s=0.0, slo_s=10.0, clock=clk)
    assert eng.extents == (1, 2, 4, 8)
    assert eng.warmup() == 4
    rid = eng.submit(np.zeros((7, 32, 32, 3), np.float32))
    eng.step()                           # 7 real rows -> extent 8
    assert eng.take(rid) is not None
    snap = eng.snapshot()
    assert snap["scheduler"] == "continuous"
    assert snap["batches"]["real_rows"] == 7
    assert snap["batches"]["dispatched_rows"] == 8   # 1 tile-pad row
    assert snap["batches"]["pad_row_fraction"] == pytest.approx(1 / 8)
    assert snap["batches"]["per_bucket"] == {8: 1}   # keyed on extent
    assert snap["executors"]["compiles"] == 4        # none past warmup
    assert snap["slo"]["slo_s"] == 10.0
    assert snap["slo"]["images_within_slo"] == 7


def test_queue_wait_counted_once_for_a_split_request(fused_params):
    """A request's queue wait runs from submit to the first dispatch
    that carries its rows: the second batch of a split request adds
    nothing; a request first dispatched in that batch adds its own."""
    clk = FakeClock()
    eng = ContinuousServingEngine(fused_params, engine="xla", max_rows=4,
                                  max_wait_s=1.0, clock=clk)
    a = eng.submit(np.zeros((6, 32, 32, 3), np.float32))
    clk.advance(0.25)
    eng.step()                           # full batch: 4 of a's 6 rows
    clk.advance(0.5)
    b = eng.submit(np.zeros((1, 32, 32, 3), np.float32))
    clk.advance(0.5)                     # a has waited out max_wait
    eng.step()                           # a's last 2 rows + b
    assert eng.take(a) is not None and eng.take(b) is not None
    assert eng.stats.dispatched_batches == 2
    wait = eng.snapshot()["queue_wait_s"]
    assert wait["count"] == 2
    assert eng.stats.queue_wait_s == pytest.approx(0.25 + 0.5)
    assert wait["mean"] == pytest.approx(0.375)
    assert wait["max"] == pytest.approx(0.5)


def test_continuous_engine_counts_rejections(fused_params):
    eng = ContinuousServingEngine(fused_params, engine="xla", max_rows=4,
                                  max_queue_rows=4, max_wait_s=10.0,
                                  clock=FakeClock())
    eng.submit(np.zeros((3, 32, 32, 3), np.float32))
    with pytest.raises(QueueFull):
        eng.submit(np.zeros((2, 32, 32, 3), np.float32))
    snap = eng.snapshot()
    assert snap["requests"]["rejected"] == 1
    assert snap["requests"]["images_rejected"] == 2
    assert snap["requests"]["submitted"] == 1        # never entered queue


def test_continuous_engine_cancel_split_request(fused_params, images):
    clk = FakeClock()
    eng = ContinuousServingEngine(fused_params, engine="xla", max_rows=4,
                                  max_wait_s=10.0, clock=clk)
    imgs = np.asarray(images)
    big = eng.submit(imgs[:6])           # 6 > budget 4: splits
    eng.step()                           # full 4-row dispatch; 2 queued
    assert eng.cancel(big)
    small = eng.submit(imgs[6:8])
    done = eng.drain()
    assert done == [small]
    want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(imgs[6:8])))
    np.testing.assert_array_equal(eng.take(small), want)
    assert eng.take(big) is None
