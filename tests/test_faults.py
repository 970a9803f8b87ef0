"""Resilience layer (DESIGN.md §11): deterministic fault injection,
deadlines, bounded retry with backoff, bit-identical engine failover,
and elastic mesh shrink — all under fake clocks so every schedule
replays exactly."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.bnn import (
    SERVE_FALLBACKS,
    bnn_apply_fused,
    init_bnn_params,
    pack_bnn_params_fused,
    pack_bnn_params_megakernel,
)
from repro.distributed.fault_tolerance import (
    serving_shrink_plan,
    shrink_serving_mesh,
)
from repro.launch.mesh import make_serving_mesh
from repro.serve import (
    ContinuousServingEngine,
    DeadlineExceeded,
    DeviceLost,
    FallbackPolicy,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    NaNLogits,
    QueueFull,
    RequestFailed,
    RetryPolicy,
    ServeStats,
    ServingEngine,
    is_error,
)

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
def mega_params():
    return pack_bnn_params_megakernel(init_bnn_params(KEY))


@pytest.fixture(scope="module")
def images():
    return np.asarray(
        jax.random.normal(jax.random.fold_in(KEY, 1), (8, 32, 32, 3))
    )


def oracle(fused_params, imgs):
    return np.asarray(bnn_apply_fused(fused_params, jnp.asarray(imgs)))


# ---------------------------------------------------------------------------
# FaultSpec / FaultPlan — pure policy
# ---------------------------------------------------------------------------

def test_fault_spec_matching_window_and_wildcards():
    s = FaultSpec("raise", at=3, count=2)
    assert not s.matches(2, 8, "xla")
    assert s.matches(3, 8, "xla")
    assert s.matches(4, 1, "xnor")       # extent/engine are wildcards
    assert not s.matches(5, 8, "xla")
    pinned = FaultSpec("nan", at=0, count=10, extent=8, engine="xla")
    assert pinned.matches(0, 8, "xla")
    assert not pinned.matches(0, 4, "xla")
    assert not pinned.matches(0, 8, "megakernel_xla")
    with pytest.raises(ValueError):
        FaultSpec("segfault")


def test_fault_plan_specs_win_over_random():
    plan = FaultPlan([FaultSpec("raise", at=1)], rate=1.0, seed=0)
    hit = plan.match(1, 8, "xla")
    assert hit is not None and hit.kind == "raise" and hit.at == 1
    # index 0 has no spec but rate=1.0 always fires randomly
    assert plan.match(0, 8, "xla") is not None


def test_fault_plan_random_schedule_is_deterministic():
    """The random layer is a pure function of (seed, index): two plans
    agree index by index, retries cannot reshuffle the schedule, and a
    different seed gives a different schedule."""
    a = FaultPlan(rate=0.3, seed=7)
    b = FaultPlan(rate=0.3, seed=7)
    sched_a = [getattr(a.match(i, 8, "xla"), "kind", None) for i in range(64)]
    # consult b out of order and repeatedly — same answers
    for i in reversed(range(64)):
        b.match(i, 8, "xla")
    sched_b = [getattr(b.match(i, 8, "xla"), "kind", None) for i in range(64)]
    assert sched_a == sched_b
    assert any(k is not None for k in sched_a)
    assert any(k is None for k in sched_a)
    c = FaultPlan(rate=0.3, seed=8)
    sched_c = [getattr(c.match(i, 8, "xla"), "kind", None) for i in range(64)]
    assert sched_a != sched_c
    assert all(k in (None, "raise", "nan", "latency") for k in sched_a)


def test_fault_plan_validates_inputs():
    with pytest.raises(ValueError):
        FaultPlan(rate=1.5)
    with pytest.raises(ValueError):
        FaultPlan(rate=0.1, kinds=("raise", "explode"))
    assert FaultPlan(rate=0.0).match(0, 8, "xla") is None


def test_fault_plan_records_fired_schedule():
    plan = FaultPlan([FaultSpec("latency", at=2, latency_s=0.5)])
    spec = plan.match(2, 4, "xla")
    plan.on_fire(2, spec, 4, "xla")
    assert plan.fired == [
        {"index": 2, "kind": "latency", "extent": 4, "engine": "xla"}
    ]


# ---------------------------------------------------------------------------
# RetryPolicy — capped exponential backoff, deterministic jitter
# ---------------------------------------------------------------------------

def test_retry_backoff_capped_exponential_without_jitter():
    p = RetryPolicy(backoff_base_s=0.1, backoff_cap_s=0.5, jitter=0.0)
    assert p.delay_s(1, 0) == pytest.approx(0.1)
    assert p.delay_s(2, 1) == pytest.approx(0.2)
    assert p.delay_s(3, 2) == pytest.approx(0.4)
    assert p.delay_s(4, 3) == pytest.approx(0.5)   # capped
    assert p.delay_s(9, 4) == pytest.approx(0.5)


def test_retry_backoff_jitter_is_bounded_and_deterministic():
    p = RetryPolicy(backoff_base_s=0.1, backoff_cap_s=1.0, jitter=0.25,
                    seed=3)
    for event in range(32):
        d = p.delay_s(1, event)
        assert 0.075 <= d <= 0.125
        assert d == p.delay_s(1, event)   # same event -> same delay
    assert len({p.delay_s(1, e) for e in range(32)}) > 1
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.0)


# ---------------------------------------------------------------------------
# FallbackPolicy — the demotion ladder
# ---------------------------------------------------------------------------

def test_fallback_ladder_walks_serve_fallbacks():
    fb = FallbackPolicy(fused_params={"p": 1}, mega_params={"m": 1})
    assert fb.next_engine("megakernel") == "xnor"
    assert fb.next_engine("megakernel_xla") == "xla"
    assert fb.next_engine("xnor") == "xla"
    assert fb.next_engine("xla") is None
    assert SERVE_FALLBACKS["xla"] == ()


def test_fallback_ladder_skips_rungs_without_params():
    fused_only = FallbackPolicy(fused_params={"p": 1})
    assert fused_only.next_engine("megakernel") == "xnor"
    assert fused_only.params_for("xnor") == {"p": 1}
    with pytest.raises(ValueError):
        fused_only.params_for("megakernel")
    mega_only = FallbackPolicy(mega_params={"m": 1})
    # fused rungs unavailable: megakernel has nowhere to go
    assert mega_only.next_engine("megakernel") is None
    with pytest.raises(ValueError):
        FallbackPolicy(failures_before_demote=0)


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------

def test_deadline_expires_queued_request(fused_params, images):
    clk = FakeClock()
    eng = ServingEngine(fused_params, buckets=(8,), max_wait_s=10.0,
                        clock=clk)
    rid = eng.submit(images[:2], deadline_s=1.0)
    clk.advance(2.0)
    resolved = eng.step()
    assert resolved == [rid]
    res = eng.take(rid)
    assert isinstance(res, DeadlineExceeded) and is_error(res)
    assert res.deadline_s == 1.0 and res.waited_s == pytest.approx(2.0)
    snap = eng.snapshot()
    assert snap["requests"]["expired"] == 1
    assert snap["requests"]["images_expired"] == 2
    # the expired request left the queue: a later drain serves nothing
    assert eng.drain() == []


def test_deadline_enforced_at_dispatch_time(fused_params, images):
    """A request whose deadline passes after batching but before
    dispatch is dropped at the pump, and its batchmate is served
    bit-identically."""
    clk = FakeClock()
    eng = ServingEngine(fused_params, buckets=(2,), max_wait_s=10.0,
                        clock=clk)
    doomed = eng.submit(images[:1], deadline_s=1.0)
    safe = eng.submit(images[1:2])
    batches = eng.batcher.poll()       # full bucket of 2 assembled
    assert len(batches) == 1
    clk.advance(5.0)                   # deadline passes pre-dispatch
    eng._run(batches)
    assert isinstance(eng.take(doomed), DeadlineExceeded)
    np.testing.assert_array_equal(
        eng.take(safe), oracle(fused_params, images[1:2]))
    snap = eng.snapshot()
    assert snap["requests"]["expired"] == 1
    assert snap["requests"]["completed"] == 1


def test_engine_default_deadline_applies_to_every_submit(fused_params,
                                                         images):
    clk = FakeClock()
    eng = ServingEngine(fused_params, buckets=(8,), max_wait_s=10.0,
                        deadline_s=1.0, clock=clk)
    rid = eng.submit(images[:1])               # inherits engine default
    slow = eng.submit(images[1:2], deadline_s=50.0)   # per-request wins
    clk.advance(2.0)
    eng.step()
    assert isinstance(eng.take(rid), DeadlineExceeded)
    eng.drain()
    np.testing.assert_array_equal(
        eng.take(slow), oracle(fused_params, images[1:2]))


def test_cancel_clears_deadline_state(fused_params, images):
    clk = FakeClock()
    eng = ServingEngine(fused_params, buckets=(8,), max_wait_s=10.0,
                        clock=clk)
    rid = eng.submit(images[:1], deadline_s=1.0)
    assert eng.cancel(rid)
    clk.advance(5.0)
    assert eng.step() == []
    assert eng.take(rid) is None       # cancelled, not expired


# ---------------------------------------------------------------------------
# Retry with backoff
# ---------------------------------------------------------------------------

def test_transient_fault_retries_to_bit_identical_success(fused_params,
                                                          images):
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=1.0, jitter=0.0),
        faults=FaultPlan([FaultSpec("raise", at=0)], sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    assert eng.step() == []            # dispatch 0 faults -> backoff
    # backoff has not elapsed: the queue head blocks, nothing dispatches
    assert eng.step() == []
    assert eng.take(rid) is None
    clk.advance(1.5)
    assert eng.step() == [rid]
    np.testing.assert_array_equal(
        eng.take(rid), oracle(fused_params, images[:2]))
    snap = eng.snapshot()
    assert snap["dispatch"]["retries"] == 1
    assert snap["requests"]["retried"] == 1
    assert snap["requests"]["failed"] == 0
    assert snap["degraded"] is False   # a retry alone is not degraded
    assert eng.faults.fired[0]["kind"] == "raise"


def test_nan_fault_is_retried_not_served(fused_params, images):
    """NaN logits never reach a caller: the guard converts them into a
    retryable failure and the retry serves clean bits."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=0.0, jitter=0.0),
        faults=FaultPlan([FaultSpec("nan", at=0)], sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    eng.step()
    eng.drain()
    out = eng.take(rid)
    assert not is_error(out)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, oracle(fused_params, images[:2]))


def test_nan_guard_catches_corrupted_executor(fused_params, images):
    """The guard is always-on, not fault-plan-only: a kernel silently
    producing non-finite logits fails the dispatch."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=1),
    )
    real_run = eng.executors.run
    eng.executors.run = lambda x: np.full((x.shape[0], 10), np.nan,
                                          np.float32)
    rid = eng.submit(images[:2])
    eng.step()
    eng.executors.run = real_run
    res = eng.take(rid)
    assert isinstance(res, RequestFailed)
    assert "NaNLogits" in res.error


def test_retry_exhaustion_fails_requests_and_engine_survives(fused_params,
                                                             images):
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=2, backoff_base_s=0.0, jitter=0.0),
        faults=FaultPlan([FaultSpec("raise", at=0, count=2)],
                         sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    eng.step()
    eng.drain()
    res = eng.take(rid)
    assert isinstance(res, RequestFailed)
    assert res.attempts == 2 and "InjectedFault" in res.error
    snap = eng.snapshot()
    assert snap["requests"]["failed"] == 1
    assert snap["requests"]["images_failed"] == 2
    # the engine is not poisoned: the next request serves cleanly
    rid2 = eng.submit(images[2:4])
    eng.step()
    eng.drain()
    np.testing.assert_array_equal(
        eng.take(rid2), oracle(fused_params, images[2:4]))


def test_queue_wait_counted_once_when_the_batch_is_retried(fused_params,
                                                          images):
    """A retried batch's requests keep the wait up to its first attempt:
    the redispatch after the backoff counts nothing more."""
    clk = FakeClock()
    eng = ContinuousServingEngine(
        fused_params, engine="xla", max_rows=4, max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=1.0, jitter=0.0),
        faults=FaultPlan([FaultSpec("raise", at=0)], sleep=clk.advance),
    )
    rid = eng.submit(images[:3])
    clk.advance(0.3)
    eng.step()                           # dispatch 0 raises; backoff
    assert eng.stats.batch_retries == 1
    clk.advance(2.0)
    eng.step()                           # dispatch 1 serves
    assert not is_error(eng.take(rid))
    wait = eng.snapshot()["queue_wait_s"]
    assert wait["count"] == 1
    assert wait["mean"] == pytest.approx(0.3)
    assert wait["max"] == pytest.approx(0.3)


def test_failed_batch_does_not_strand_batchmates(fused_params, images):
    """Regression for the §11 bugfix: one poisoned batch completes its
    own riders as RequestFailed and the NEXT batch in the same pump
    still dispatches — a dispatch exception no longer unwinds the loop
    and strands everything behind it."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=1),
        faults=FaultPlan([FaultSpec("raise", at=0)], sleep=clk.advance),
    )
    poisoned = eng.submit(images[:2])
    healthy = eng.submit(images[2:4])
    resolved = eng.step()              # two full buckets in one poll
    assert set(resolved) == {poisoned, healthy}
    assert isinstance(eng.take(poisoned), RequestFailed)
    np.testing.assert_array_equal(
        eng.take(healthy), oracle(fused_params, images[2:4]))


def test_backoff_preserves_fifo_order(fused_params, images):
    """A batch in backoff blocks the queue head: later batches must not
    overtake it, so completion order among successes stays FIFO."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=1.0, jitter=0.0),
        faults=FaultPlan([FaultSpec("raise", at=0)], sleep=clk.advance),
    )
    first = eng.submit(images[:2])
    eng.step()                         # first batch faults, backs off
    second = eng.submit(images[2:4])
    assert eng.step() == []            # second must wait behind first
    clk.advance(1.5)
    resolved = eng.step()
    assert resolved == [first, second]
    np.testing.assert_array_equal(
        eng.take(first), oracle(fused_params, images[:2]))
    np.testing.assert_array_equal(
        eng.take(second), oracle(fused_params, images[2:4]))


def test_drain_forces_through_backoff(fused_params, images):
    """drain() must leave nothing unresolved even when backoff has not
    elapsed on the fake clock."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=1e9, jitter=0.0),
        faults=FaultPlan([FaultSpec("raise", at=0)], sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    eng.step()
    assert eng.step() == []            # blocked behind a huge backoff
    assert eng.drain() == [rid]
    np.testing.assert_array_equal(
        eng.take(rid), oracle(fused_params, images[:2]))


def test_latency_fault_goes_through_sleep_hook(fused_params, images):
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        faults=FaultPlan([FaultSpec("latency", at=0, latency_s=3.0)],
                         sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    eng.step()
    assert clk.t == pytest.approx(3.0)     # slept on the fake clock
    np.testing.assert_array_equal(
        eng.take(rid), oracle(fused_params, images[:2]))


# ---------------------------------------------------------------------------
# Engine failover
# ---------------------------------------------------------------------------

def test_failover_demotes_and_serves_bit_identical(fused_params,
                                                   mega_params, images):
    """Two consecutive megakernel_xla failures demote to xla; because
    every rung is bit-identical, post-failover logits match the fused
    oracle exactly."""
    clk = FakeClock()
    eng = ServingEngine(
        mega_params, engine="megakernel_xla", buckets=(2,),
        max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=5, backoff_base_s=0.0, jitter=0.0),
        fallback=FallbackPolicy(fused_params=fused_params,
                                mega_params=mega_params,
                                failures_before_demote=2),
        faults=FaultPlan(
            [FaultSpec("raise", at=0, count=2, engine="megakernel_xla")],
            sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    eng.step()
    eng.drain()
    assert eng.executors.engine == "xla"
    np.testing.assert_array_equal(
        eng.take(rid), oracle(fused_params, images[:2]))
    snap = eng.snapshot()
    assert snap["dispatch"]["fallbacks"] == 1
    assert snap["dispatch"]["engine_path"] == ["megakernel_xla->xla"]
    assert snap["degraded"] is True


def test_failover_hot_standby_swaps_without_recompile(fused_params,
                                                      mega_params, images):
    """prewarm_fallback builds the next rung ahead of time; the later
    demotion swaps it in and serving continues with ZERO new compiles."""
    clk = FakeClock()
    eng = ServingEngine(
        mega_params, engine="megakernel_xla", buckets=(2,),
        max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=5, backoff_base_s=0.0, jitter=0.0),
        fallback=FallbackPolicy(fused_params=fused_params,
                                mega_params=mega_params,
                                failures_before_demote=2),
        faults=FaultPlan(
            [FaultSpec("raise", at=0, count=2, engine="megakernel_xla")],
            sleep=clk.advance),
    )
    eng.warmup()
    assert eng.prewarm_fallback() > 0
    standby = eng._standby
    assert standby is not None and standby.engine == "xla"
    compiled_before = len(standby._fns)
    eng.submit(images[:2])
    eng.step()
    eng.drain()                        # dispatch 0,1 fault -> demote
    assert eng.executors is standby    # the hot standby was swapped in
    assert eng._standby is None
    rid = eng.submit(images[2:4])
    eng.step()
    eng.drain()
    np.testing.assert_array_equal(
        eng.take(rid), oracle(fused_params, images[2:4]))
    assert len(eng.executors._fns) == compiled_before   # no new compiles


def test_ladder_exhausted_engine_fails_requests(fused_params, images):
    """On the bottom rung (xla) with nowhere to demote, a persistent
    fault exhausts retries into RequestFailed — no demotion loop."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, engine="xla", buckets=(2,), max_wait_s=0.0,
        clock=clk,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=0.0, jitter=0.0),
        fallback=FallbackPolicy(fused_params=fused_params,
                                failures_before_demote=1),
        faults=FaultPlan([FaultSpec("raise", at=0, count=5)],
                         sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    eng.step()
    eng.drain()
    assert isinstance(eng.take(rid), RequestFailed)
    assert eng.executors.engine == "xla"
    assert eng.snapshot()["dispatch"]["fallbacks"] == 0


# ---------------------------------------------------------------------------
# Elastic mesh shrink
# ---------------------------------------------------------------------------

def test_serving_shrink_plan_largest_power_of_two():
    assert serving_shrink_plan(8) == 8
    assert serving_shrink_plan(7) == 4
    assert serving_shrink_plan(4) == 4
    assert serving_shrink_plan(3) == 2
    assert serving_shrink_plan(1) == 1
    assert serving_shrink_plan(0) == 0


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (conftest forces 8 host "
                           "devices before any jax import)")
def test_shrink_serving_mesh_helper():
    mesh = make_serving_mesh(8)
    shrunk = shrink_serving_mesh(mesh, (5,))
    assert shrunk.shape == {"data": 4}      # 7 survivors -> 4
    dead5 = set(np.asarray(shrunk.devices).flat)
    assert np.asarray(mesh.devices).flat[5] not in dead5
    assert shrink_serving_mesh(mesh, (99,)) is None   # invalid index
    one = make_serving_mesh(1)
    assert shrink_serving_mesh(one, (0,)) is None     # nothing survives


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (conftest forces 8 host "
                           "devices before any jax import)")
def test_device_loss_shrinks_mesh_and_redispatches(fused_params, images):
    """A DeviceLost dispatch shrinks 8 -> 4, re-dispatches the in-flight
    batch without charging its retry budget, and steady state on the
    shrunk mesh adds zero compiles after the re-warm."""
    clk = FakeClock()
    eng = ContinuousServingEngine(
        fused_params, engine="xla", max_rows=8, max_wait_s=0.0,
        mesh=make_serving_mesh(8), clock=clk,
        retry=RetryPolicy(max_attempts=1),   # loss must not burn it
        faults=FaultPlan([FaultSpec("device_loss", at=1, device=5)],
                         sleep=clk.advance),
    )
    eng.warmup()
    a = eng.submit(images[:3])
    eng.step()
    eng.drain()                        # dispatch 0 clean
    b = eng.submit(images[3:6])
    eng.step()
    eng.drain()                        # dispatch 1 loses device 5
    np.testing.assert_array_equal(
        eng.take(a), oracle(fused_params, images[:3]))
    np.testing.assert_array_equal(
        eng.take(b), oracle(fused_params, images[3:6]))
    assert eng.executors.devices == 4
    snap = eng.snapshot()
    assert snap["mesh"]["shrinks"] == 1
    assert snap["mesh"]["devices"] == 4
    assert snap["degraded"] is True
    assert snap["requests"]["failed"] == 0
    # extent ladder recomputed at the survivor multiple
    assert all(e % 4 == 0 for e in eng.extents)
    # steady state on the shrunk mesh: zero further compiles
    compiled = len(eng.executors._fns)
    c = eng.submit(images[:5])
    eng.step()
    eng.drain()
    np.testing.assert_array_equal(
        eng.take(c), oracle(fused_params, images[:5]))
    assert len(eng.executors._fns) == compiled


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (conftest forces 8 host "
                           "devices before any jax import)")
def test_heartbeat_timeout_triggers_shrink(fused_params, images):
    """A device that stops beating is treated like a mid-dispatch loss:
    the next step() shrinks the mesh before dispatching."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, engine="xla", buckets=(8,), max_wait_s=0.0,
        mesh=make_serving_mesh(8), heartbeat_timeout_s=10.0, clock=clk,
    )
    assert eng.monitor is not None
    clk.advance(5.0)
    for dev in range(8):
        if dev != 3:
            eng.beat(dev)
    clk.advance(7.0)                   # device 3 silent past timeout
    rid = eng.submit(images)
    eng.step()
    eng.drain()
    assert eng.executors.devices == 4
    assert eng.snapshot()["mesh"]["shrinks"] == 1
    # the monitor was rebuilt for the shrunk mesh
    assert len(eng.monitor.last_beat) == 4
    np.testing.assert_array_equal(
        eng.take(rid), oracle(fused_params, images))


def test_device_loss_without_mesh_is_ordinary_failure(fused_params, images):
    """Unmeshed engine: DeviceLost cannot shrink, so it burns retry
    budget like any other dispatch failure."""
    clk = FakeClock()
    eng = ServingEngine(
        fused_params, buckets=(2,), max_wait_s=0.0, clock=clk,
        retry=RetryPolicy(max_attempts=1),
        faults=FaultPlan([FaultSpec("device_loss", at=0, device=0)],
                         sleep=clk.advance),
    )
    rid = eng.submit(images[:2])
    eng.step()
    res = eng.take(rid)
    assert isinstance(res, RequestFailed) and "DeviceLost" in res.error


# ---------------------------------------------------------------------------
# Admission control backoff hint
# ---------------------------------------------------------------------------

def test_queuefull_hint_falls_back_to_max_wait(fused_params, images):
    clk = FakeClock()
    eng = ContinuousServingEngine(
        fused_params, max_rows=4, max_wait_s=0.25, max_queue_rows=4,
        clock=clk,
    )
    eng.submit(images[:4])
    with pytest.raises(QueueFull) as exc:
        eng.submit(images[4:6])
    # no service observation yet: hint degrades to the coalescing wait
    assert exc.value.retry_after_s == pytest.approx(0.25)
    assert eng.snapshot()["requests"]["rejected"] == 1


def test_queuefull_hint_uses_service_ewma(fused_params, images):
    clk = FakeClock()
    eng = ContinuousServingEngine(
        fused_params, max_rows=4, max_wait_s=0.25, max_queue_rows=4,
        clock=clk,
    )
    eng.batcher.note_service(4, 2.0)       # 0.5 s/row observed
    eng.submit(images[:4])
    with pytest.raises(QueueFull) as exc:
        eng.submit(images[4:6])            # 2 rows past the bound
    assert exc.value.retry_after_s == pytest.approx(
        eng.batcher.est_service_s(2))
    assert exc.value.retry_after_s == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Stats surface
# ---------------------------------------------------------------------------

def test_stats_resilience_counters_and_degraded_flag():
    s = ServeStats()
    snap = s.snapshot()
    assert snap["requests"]["expired"] == 0
    assert snap["requests"]["failed"] == 0
    assert snap["requests"]["retried"] == 0
    assert snap["dispatch"]["retries"] == 0
    assert snap["dispatch"]["fallbacks"] == 0
    assert snap["dispatch"]["engine_path"] == []
    assert snap["mesh"]["shrinks"] == 0
    assert snap["degraded"] is False
    s.on_expire(3)
    s.on_fail(2)
    s.on_retry(4)
    s.on_fallback("megakernel", "xnor")
    s.on_fallback("xnor", "xla")
    s.on_shrink(8, 4)
    snap = s.snapshot()
    assert snap["requests"]["expired"] == 1
    assert snap["requests"]["images_expired"] == 3
    assert snap["requests"]["failed"] == 1
    assert snap["requests"]["images_failed"] == 2
    assert snap["requests"]["retried"] == 4
    assert snap["dispatch"]["retries"] == 1
    assert snap["dispatch"]["fallbacks"] == 2
    assert snap["dispatch"]["engine_path"] == ["megakernel->xnor",
                                               "xnor->xla"]
    assert snap["mesh"]["shrinks"] == 1
    assert snap["mesh"]["devices"] == 4
    assert snap["degraded"] is True
