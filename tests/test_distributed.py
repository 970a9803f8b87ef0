"""Distribution substrate: sharding rules, gradient compression,
fault tolerance, elastic meshes, checkpointing."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.checkpoint import manager as ckpt
from repro.distributed import compression, sharding
from repro.distributed.fault_tolerance import (
    HeartbeatMonitor,
    StragglerDetector,
    WorkerFailure,
    plan_mesh_for,
    run_with_recovery,
)


def _fake_mesh():
    """An abstract mesh shape for rule checks (1 real device is fine —
    specs are pure metadata)."""
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1, 1)
    m = Mesh(dev, ("pod", "data", "model"))
    # monkey-patch shape lookups: rules only read mesh.shape
    return m


class _ShapeMesh:
    """Duck-typed mesh exposing only .shape for the rule functions."""

    def __init__(self, **axes):
        self.shape = axes


MESH = _ShapeMesh(pod=2, data=16, model=16)


def _leaf(*shape):
    # The rule functions read only np.shape(leaf); an abstract value
    # keeps frontier-scale cases (the FSDP one is 1.25 TB dense) from
    # actually allocating.
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_column_parallel_weight_spec():
    spec = sharding.param_spec(
        MESH, _path(["layers", 0, "attn", "q_proj", "w"]),
        _leaf(22, 12288, 12288),
    )
    assert spec == P(None, "model", ("pod", "data"))


def test_row_parallel_weight_spec():
    spec = sharding.param_spec(
        MESH, _path(["layers", 0, "ffn", "down_proj", "w"]),
        _leaf(22, 12288, 28672),
    )
    assert spec == P(None, ("pod", "data"), "model")


def test_expert_stack_spec_small_replicates_over_data():
    # moonshot-sized stack (184M elems): E over model, in-dim NOT FSDP'd
    # — FSDP there forces an [E,cap,d] partial-sum all-reduce per layer
    # (§Perf hc7)
    spec = sharding.param_spec(
        MESH, _path(["layers", 0, "moe", "up_proj", "w"]),
        _leaf(48, 64, 1408, 2048),
    )
    assert spec == P(None, "model", None, None)


def test_expert_stack_spec_big_gets_fsdp():
    # arctic-sized stack (4.5e9 elems): too big to replicate over data
    spec = sharding.param_spec(
        MESH, _path(["layers", 0, "moe", "up_proj", "w"]),
        _leaf(35, 128, 4864, 7168),
    )
    assert spec == P(None, "model", None, ("pod", "data"))


def test_indivisible_axis_left_unsharded():
    # 15 heads * 64 = 960 divides 16; but a dim of 17 must not shard
    spec = sharding.param_spec(
        MESH, _path(["q_proj", "w"]), np.zeros((17, 960)))
    assert spec == P(None, ("pod", "data"))


def test_packed_weight_spec_replicated_over_data():
    spec = sharding.param_spec(
        MESH, _path(["ffn", "up_proj", "w_packed"]), np.zeros((2560, 30)))
    assert spec == P("model", None)


def test_kv_cache_spec():
    spec = sharding.state_spec(
        MESH, _path(["kv", "k"]), _leaf(8, 128, 1024, 8, 128))
    assert spec == P(None, ("pod", "data"), "model", None, None)


def test_kv_cache_batch1_seq_sharded():
    spec = sharding.state_spec(
        MESH, _path(["kv", "k"]), np.zeros((8, 1, 2048, 8, 128)))
    assert spec == P(None, None, "model", None, None)


def test_serve_specs_replicate_weights_shard_batch():
    # DESIGN.md §10: the serving mesh contract — packed weights P()
    # on every device, batch axis over "data", collective-free.
    p_spec, x_spec, y_spec = sharding.serve_specs(_ShapeMesh(data=8))
    assert p_spec == P()
    assert x_spec == P("data") and y_spec == P("data")
    # a mesh without a "data" axis degrades to fully replicated
    p_spec, x_spec, y_spec = sharding.serve_specs(_ShapeMesh(model=4))
    assert (p_spec, x_spec, y_spec) == (P(), P(None), P(None))


def test_mesh_devices_counts_all_axes():
    assert sharding.mesh_devices(None) == 1
    assert sharding.mesh_devices(_ShapeMesh(data=8)) == 8
    assert sharding.mesh_devices(MESH) == 2 * 16 * 16


def _path(keys):
    out = []
    for k in keys:
        if isinstance(k, int):
            out.append(jax.tree_util.SequenceKey(k))
        else:
            out.append(jax.tree_util.DictKey(k))
    return tuple(out)


# ------------------------------ compression ----------------------------------


def test_compression_error_feedback_reduces_bias():
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(0, 1, (256,)).astype(np.float32))
    err = jnp.zeros_like(g_true)
    acc = jnp.zeros_like(g_true)
    for _ in range(50):
        deq, err = compression.compress_decompress(g_true, err)
        acc = acc + deq
    # with error feedback the running sum converges to 50*g
    np.testing.assert_allclose(acc / 50, g_true, atol=1e-2)


def test_compression_single_round_is_int8_coarse():
    g = jnp.linspace(-1, 1, 255)
    deq, err = compression.compress_decompress(g, jnp.zeros_like(g))
    assert float(jnp.max(jnp.abs(err))) <= float(jnp.max(jnp.abs(g))) / 127


def test_psum_compressed_in_shard_map():
    if jax.device_count() < 1:
        pytest.skip("no devices")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    g = jnp.arange(8, dtype=jnp.float32)

    def f(g):
        mean, err = compression.psum_compressed(g, jnp.zeros_like(g), "data")
        return mean

    out = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(g)
    np.testing.assert_allclose(out, g, atol=0.05)


def test_signsgd_error_feedback_reduces_bias():
    """EF-sign-SGD (ISSUE 9): one round keeps only 1 bit/coordinate, but
    with error feedback the running sum of decompressed grads converges
    to the true gradient — the residual carries everything the sign
    threw away into later rounds.

    Unlike int8 (whose per-round error is already bounded by half a
    quantization step), a 1-bit code with one SHARED scale makes small
    coordinates oscillate around their true value — so the guarantee is
    the EF one: the time-averaged decompressed gradient converges, and
    keeps improving with more rounds (measured: mean |avg - g| of
    0.041 / 0.013 / 0.004 at 50 / 200 / 800 rounds)."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(0, 1, (256,)).astype(np.float32))

    def avg_error(rounds):
        err = jnp.zeros_like(g_true)
        acc = jnp.zeros_like(g_true)
        for _ in range(rounds):
            deq, err = compression.signsgd_compress_decompress(g_true, err)
            acc = acc + deq
        return float(jnp.mean(jnp.abs(acc / rounds - g_true)))

    e50, e800 = avg_error(50), avg_error(800)
    assert e50 < 5e-2
    assert e800 < 1e-2
    assert e800 < e50 / 4  # genuinely converging, not plateaued


def test_signsgd_single_round_is_scaled_sign():
    g = jnp.linspace(-1.0, 1.0, 255)
    deq, err = compression.signsgd_compress_decompress(g, jnp.zeros_like(g))
    scale = float(jnp.mean(jnp.abs(g)))
    np.testing.assert_allclose(
        np.asarray(deq), scale * np.sign(np.where(g == 0, 1.0, g)),
        rtol=1e-6,
    )
    # lossless in the EF sense: deq + err reconstructs g exactly
    np.testing.assert_allclose(np.asarray(deq + err), np.asarray(g),
                               atol=1e-6)


def test_psum_signsgd_in_shard_map():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    g = jnp.arange(8, dtype=jnp.float32) - 3.5

    def f(g):
        mean, err = compression.psum_signsgd(g, jnp.zeros_like(g), "data")
        return mean, err

    mean, err = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(g)
    # single device: mean == scale * sign(g), and EF reconstructs g
    scale = float(jnp.mean(jnp.abs(g)))
    np.testing.assert_allclose(
        np.asarray(mean), scale * np.where(np.asarray(g) >= 0, 1.0, -1.0),
        rtol=1e-6,
    )
    np.testing.assert_allclose(np.asarray(mean + err), np.asarray(g),
                               atol=1e-6)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices")
def test_signsgd_convergence_tracks_fp32():
    """Convergence gate (ISSUE 9): plain SGD on a 2-device least-squares
    problem, gradients all-reduced three ways — fp32 pmean, EF-int8, and
    1-bit EF-sign-SGD. Both compressed runs must reach (near) the fp32
    baseline's final loss: error feedback is exactly what makes 1-bit
    gradients usable, and this is the test that would catch losing it."""
    n_dev, n, d, lr, steps = 2, 64, 8, 0.05, 300
    rng = np.random.default_rng(3)
    w_true = rng.normal(size=(d,)).astype(np.float32)
    x = rng.normal(size=(n_dev, n, d)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=(n_dev, n)).astype(np.float32)
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("data",))

    def run(reduce_fn):
        def shard_step(w, err, xs, ys):
            xs, ys = xs[0], ys[0]          # peel the shard axis
            g = 2.0 * xs.T @ (xs @ w - ys) / xs.shape[0]
            g, new_err = reduce_fn(g, err[0])
            return w - lr * g, new_err[None]

        step = jax.jit(jax.shard_map(
            shard_step, mesh=mesh,
            in_specs=(P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P("data")),
            check_vma=False,
        ))
        w = jnp.zeros((d,))
        err = jnp.zeros((n_dev, d))
        for _ in range(steps):
            w, err = step(w, err, x, y)
        resid = x.reshape(-1, d) @ w - y.reshape(-1)
        return float(jnp.mean(resid**2))

    loss_fp32 = run(lambda g, e: (jax.lax.pmean(g, "data"), e))
    loss_int8 = run(lambda g, e: compression.psum_compressed(g, e, "data"))
    loss_sign = run(lambda g, e: compression.psum_signsgd(g, e, "data"))
    # the problem's noise floor is ~1e-4; every run must solve it
    assert loss_fp32 < 5e-4
    assert loss_int8 < 5 * loss_fp32
    assert loss_sign < 5 * loss_fp32


# ---------------------------- fault tolerance ---------------------------------


def test_heartbeat_detects_dead_host():
    t = [0.0]
    mon = HeartbeatMonitor(num_hosts=3, timeout=10.0, clock=lambda: t[0])
    t[0] = 5.0
    mon.beat(0)
    mon.beat(1)
    t[0] = 12.0
    assert mon.dead_hosts() == [2]
    with pytest.raises(WorkerFailure):
        mon.check()


def test_straggler_detector_flags_persistent_outlier():
    det = StragglerDetector(patience=3)
    flagged = []
    for _ in range(6):
        flagged = det.observe({0: 1.0, 1: 1.0, 2: 1.0, 3: 1.0, 4: 5.0})
    assert 4 in flagged


def test_straggler_detector_uniform_fleet_never_flags():
    det = StragglerDetector(patience=2)
    for _ in range(20):
        assert det.observe({h: 1.0 for h in range(8)}) == []


def test_straggler_detector_recovery_resets_strikes():
    """A host that recovers before ``patience`` consecutive slow steps
    is never flagged — the strike counter resets on every fast step."""
    det = StragglerDetector(patience=3)
    fleet = {0: 1.0, 1: 1.0, 2: 1.0, 3: 1.0}
    for _ in range(10):
        assert det.observe({**fleet, 4: 5.0}) == []   # 1 strike
        assert det.observe({**fleet, 4: 1.0}) == []   # recovered: reset
    assert det.strikes[4] == 0


def test_plan_mesh_shrinks_elastically():
    assert plan_mesh_for(512).shape == (2, 16, 16)
    assert plan_mesh_for(256).shape == (16, 16)
    assert plan_mesh_for(240).shape == (15, 16)   # lost a host: data shrinks
    assert plan_mesh_for(8).shape == (8,)


def test_run_with_recovery_restores_after_failure():
    state = {"step": 0, "saved": 0, "failures_left": 1}

    def step_fn(step):
        if step == 3 and state["failures_left"]:
            state["failures_left"] -= 1
            raise WorkerFailure([1])
        state["step"] = step
        return {"step": step}

    def save_fn(step):
        state["saved"] = step

    def restore_fn():
        return state["saved"]

    mon = HeartbeatMonitor(num_hosts=2, timeout=1e9)
    out = run_with_recovery(
        num_steps=6, step_fn=step_fn, save_fn=save_fn,
        restore_fn=restore_fn, monitor=mon, checkpoint_every=2,
    )
    assert out["step"] == 5
    assert state["failures_left"] == 0


def test_run_with_recovery_gives_up_after_max_restarts():
    def step_fn(step):
        raise WorkerFailure([0])

    mon = HeartbeatMonitor(num_hosts=1, timeout=1e9)
    with pytest.raises(WorkerFailure):
        run_with_recovery(
            num_steps=4, step_fn=step_fn, save_fn=lambda s: None,
            restore_fn=lambda: 0, monitor=mon, max_restarts=2,
        )


def test_run_with_recovery_rebuilds_and_stops_monitoring_dead_hosts():
    """On failure the driver calls ``rebuild_fn`` with the dead hosts
    and evicts them from the heartbeat monitor, so a host that died
    once cannot re-trigger WorkerFailure on the next check."""
    state = {"failures_left": 1, "rebuilt_with": None}

    def step_fn(step):
        if step == 1 and state["failures_left"]:
            state["failures_left"] -= 1
            raise WorkerFailure([2, 1])
        return {"step": step}

    mon = HeartbeatMonitor(num_hosts=3, timeout=1e9)
    out = run_with_recovery(
        num_steps=3, step_fn=step_fn, save_fn=lambda s: None,
        restore_fn=lambda: 0, monitor=mon,
        rebuild_fn=lambda hosts: state.update(rebuilt_with=hosts),
    )
    assert out["step"] == 2
    assert state["rebuilt_with"] == [1, 2]   # sorted by WorkerFailure
    assert set(mon.last_beat) == {0}


def test_run_with_recovery_checkpoint_cadence():
    saves = []
    mon = HeartbeatMonitor(num_hosts=1, timeout=1e9)
    run_with_recovery(
        num_steps=10, step_fn=lambda s: {"step": s},
        save_fn=saves.append, restore_fn=lambda: 0, monitor=mon,
        checkpoint_every=3,
    )
    assert saves == [3, 6, 9]


# ------------------------------ checkpointing ---------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((2, 3))}}
    ckpt.save(str(tmp_path), 7, tree)
    assert ckpt.latest_valid_step(str(tmp_path)) == 7
    out = ckpt.restore(str(tmp_path), 7, tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])


def test_checkpoint_torn_write_is_skipped(tmp_path):
    tree = {"a": jnp.arange(4.0)}
    ckpt.save(str(tmp_path), 1, tree)
    ckpt.save(str(tmp_path), 2, tree)
    # corrupt step 2 (simulate crash mid-write)
    os.remove(os.path.join(tmp_path, "step_00000002", "MANIFEST.json"))
    assert ckpt.latest_valid_step(str(tmp_path)) == 1


def test_checkpoint_checksum_mismatch_is_skipped(tmp_path):
    tree = {"a": jnp.arange(4.0)}
    ckpt.save(str(tmp_path), 1, tree)
    shard = os.path.join(tmp_path, "step_00000001", "shard_00000.npz")
    with open(shard, "ab") as f:
        f.write(b"corruption")
    assert ckpt.latest_valid_step(str(tmp_path)) is None


def test_async_checkpointer(tmp_path):
    w = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3):
        w.save(s, {"x": jnp.full((4,), float(s))})
    w.close()
    assert ckpt.latest_valid_step(str(tmp_path)) == 3
    # retention pruned step 1
    assert not os.path.exists(os.path.join(tmp_path, "step_00000001"))
    out = ckpt.restore(str(tmp_path), 3, {"x": jnp.zeros((4,))})
    np.testing.assert_allclose(out["x"], 3.0)


def test_stray_entries_do_not_crash_latest_valid_step(tmp_path):
    # A stray non-conforming entry in the checkpoint dir (editor
    # leftover, half-renamed staging dir) must be skipped, not crash the
    # recovery path with int("abc").
    tree = {"a": jnp.arange(4.0)}
    ckpt.save(str(tmp_path), 5, tree)
    for stray in ("step_abc", "step_", "step_7.tmp", "notes.txt"):
        p = os.path.join(tmp_path, stray)
        if stray.endswith(".txt"):
            with open(p, "w") as f:
                f.write("stray")
        else:
            os.makedirs(p)
    assert ckpt.latest_valid_step(str(tmp_path)) == 5


def test_stray_entries_do_not_crash_retain(tmp_path):
    tree = {"a": jnp.arange(4.0)}
    for s in (1, 2, 3, 4):
        ckpt.save(str(tmp_path), s, tree)
    os.makedirs(os.path.join(tmp_path, "step_abc"))
    ckpt.retain(str(tmp_path), keep=2)
    assert ckpt.latest_valid_step(str(tmp_path)) == 4
    assert not os.path.exists(os.path.join(tmp_path, "step_00000001"))
    # the stray entry is left alone (retain only manages step dirs)
    assert os.path.exists(os.path.join(tmp_path, "step_abc"))


def test_restore_schema_mismatch_is_actionable(tmp_path):
    ckpt.save(str(tmp_path), 1, {"params": {"w": jnp.ones((2,))}})
    bad_like = {"params": {"w": jnp.zeros((2,)), "extra": jnp.zeros(())}}
    with pytest.raises(ValueError) as ei:
        ckpt.restore(str(tmp_path), 1, bad_like)
    msg = str(ei.value)
    assert "params/extra" in msg        # missing from the checkpoint
    assert "missing" in msg and "unexpected" in msg


def test_async_checkpointer_save_after_close_raises(tmp_path):
    w = ckpt.AsyncCheckpointer(str(tmp_path))
    w.save(1, {"x": jnp.zeros((2,))})
    w.close()
    with pytest.raises(RuntimeError, match="after close"):
        w.save(2, {"x": jnp.ones((2,))})
    w.close()  # idempotent
