"""The serving program's spans (``repro/serve``) and their reduction
(``bench/spans.py``): a ``ContinuousServingEngine`` run under the
profiler on the CPU, on one device and on a four-device mesh; the idle
gaps of a device named by the innermost span over them; and a recorded
TPU v5e trace, 0.2 s of the backlog cell's window (40 dispatches of 32
images, engine ``megakernel``), recorded with ``bench/record_trace.py``
— it fixes the layout of the host plane on the chip."""

import glob
import pathlib

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import spans as S
from bench import trace
from bench.trace import WINDOW_SPAN
from repro.core.bnn import init_bnn_params, pack_bnn_params_megakernel
from repro.launch.mesh import make_serving_mesh
from repro.serve import ContinuousServingEngine

TRACE = (pathlib.Path(__file__).resolve().parent / "data"
         / "v5e_backlog_spans.xplane.pb")

# span -> the spans it may sit directly inside
PARENTS = {
    "serve.step": {None},
    "serve.dispatch": {"serve.step"},
    "serve.assemble": {"serve.dispatch"},
    "serve.h2d": {"serve.dispatch"},
    "serve.compile": {"serve.dispatch"},
    "serve.launch": {"serve.dispatch", "serve.compile"},
    "serve.wait": {"serve.dispatch"},
    "serve.d2h": {"serve.dispatch"},
    "serve.scatter": {"serve.dispatch"},
}


@pytest.fixture(scope="module")
def packed():
    return pack_bnn_params_megakernel(init_bnn_params(jax.random.PRNGKey(7)))


def _traced_run(packed, tmp_path, devices):
    """Serve a few requests under the profiler, nothing warmed: a 3-row
    and a 6-row request at ``max_rows`` 4 per device, so the engine
    dispatches extents 4, 4 and 2 (times the devices) and compiles two
    executors on the way."""
    mesh = make_serving_mesh(devices) if devices > 1 else None
    eng = ContinuousServingEngine(packed, engine="megakernel_xla",
                                  max_rows=4 * devices, max_wait_s=0.0,
                                  mesh=mesh)
    rng = np.random.default_rng(0)
    before = eng.stats.executor_misses
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            rids = [eng.submit(rng.normal(size=(3 * devices, 32, 32, 3))
                               .astype(np.float32))]
            eng.step()
            rids.append(eng.submit(rng.normal(size=(6 * devices, 32, 32, 3))
                                   .astype(np.float32)))
            eng.step()
    finally:
        jax.profiler.stop_trace()
    assert all(eng.take(r) is not None for r in rids)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return eng, eng.stats.executor_misses - before, ProfileData.from_file(path)


@pytest.mark.parametrize("devices", [1, 4])
def test_engine_emits_nested_spans(packed, tmp_path, devices):
    eng, misses, data = _traced_run(packed, tmp_path, devices)
    got = S.collect(data)
    names = {s.name for s in got.items}
    assert names == set(PARENTS)
    assert {S.DISPATCH, S.WAIT, S.D2H} <= names
    for s in got.items:
        parent = got.items[s.parent].name if s.parent is not None else None
        assert parent in PARENTS[s.name], (s.name, parent)
        assert s.depth == (0 if parent is None
                           else got.items[s.parent].depth + 1)
    dispatches = got.of(S.DISPATCH)
    assert len(dispatches) == eng.stats.dispatched_batches == 3
    assert [d.args["dispatch"] for d in dispatches] == [0, 1, 2]
    assert [d.args["rows"] for d in dispatches] == [
        3 * devices, 4 * devices, 2 * devices]
    assert [d.args["extent"] for d in dispatches] == [
        4 * devices, 4 * devices, 2 * devices]
    compiles = got.of("serve.compile")
    assert len(compiles) == misses == 2
    assert [c.args["extent"] for c in compiles] == [4 * devices, 2 * devices]
    # every dispatch waits once and copies once; the padded one assembles
    # its batch and then its pad rows
    for i, d in enumerate(got.items):
        if d.name != S.DISPATCH:
            continue
        kids = [s.name for s in got.items if s.parent == i]
        assert kids.count(S.WAIT) == kids.count(S.D2H) == 1
        assert kids.count("serve.assemble") == (
            2 if d.args["rows"] != d.args["extent"] else 1)
    host = got.host_seconds_per_dispatch()
    total = sum(d.seconds for d in dispatches) / 3
    waits = got.seconds(S.WAIT) / 3
    assert host == pytest.approx(total - waits)
    assert got.seconds_per_dispatch(S.D2H) == pytest.approx(
        got.seconds(S.D2H) / 3)


def test_gaps_are_named_by_the_innermost_span(packed, tmp_path):
    _, _, data = _traced_run(packed, tmp_path, 1)
    got = S.collect(data)
    w0, _ = got.window
    step = got.of("serve.step")[0]
    first = got.of(S.DISPATCH)[0]
    wait = got.of(S.WAIT)[1]
    gaps = [
        (w0, step.start_ns),                    # before any step
        (step.start_ns, first.start_ns),        # in the step, no dispatch
        (wait.start_ns + 1, wait.end_ns - 1),   # the host waits on it
    ]
    labelled = S.label_gaps(got, gaps)
    by_len = sorted(zip([(e - s) * 1e-9 for s, e in gaps],
                        [S.NO_SPAN, "serve.step", S.WAIT]),
                    key=lambda g: -g[0])
    assert [w for _, w in labelled] == [w for _, w in by_len]


def test_no_spans_read_nothing():
    empty = S.Spans(window=(0, 1), items=[])
    assert empty.host_seconds_per_dispatch() is None
    assert empty.seconds_per_dispatch(S.D2H) is None
    assert empty.seconds(S.WAIT) is None
    assert empty.innermost([0.5]) == [None]


def test_timeline_tiles_the_window_and_splits_intervals():
    def span(name, a, b):
        return S.Span(name, a, b, 0, {}, None)

    got = S.Spans(window=(0, 100), items=[
        span("serve.step", 0, 50),
        span(S.DISPATCH, 5, 20),
        span(S.WAIT, 6, 10),
    ])
    pieces = [(a, b, s.name if s else None) for a, b, s in got.timeline()]
    assert pieces == [(0, 5, "serve.step"), (5, 6, S.DISPATCH),
                      (6, 10, S.WAIT), (10, 20, S.DISPATCH),
                      (20, 50, "serve.step"), (50, 100, None)]
    split = got.overlap([(4, 8), (45, 60), (90, 100)])
    assert split == pytest.approx({
        "serve.step": 6e-9, S.DISPATCH: 1e-9, S.WAIT: 2e-9,
        S.NO_SPAN: 20e-9})


def test_offset_pairs_waits_with_the_serving_program():
    # every dispatch: the device runs an input move (ends 2-4 ms in) and
    # the serving program (ends 5 ms in); the host's wait ends 3 ms
    # after the serving run on the raw clocks. Two runs of each come
    # before the window; none after it.
    waits = [i * 20.0 + 8.0 for i in range(10)]
    runs = {"jit_apply_fn": [i * 20.0 + 5.0 for i in range(-2, 10)],
            "jit_move": [i * 20.0 + 2.0 + (i % 3) for i in range(-2, 10)]}
    assert S.offset_from(waits, runs) == pytest.approx(3.0)
    assert S.offset_from([], runs) is None
    assert S.offset_from(waits, {}) is None


def test_innermost_follows_nesting():
    def span(name, a, b, parent=None):
        return S.Span(name, a, b, 0, {}, parent)

    got = S.Spans(window=(0, 100), items=[
        span("serve.step", 0, 50),
        span(S.DISPATCH, 5, 20, 0),
        span(S.WAIT, 6, 10, 1),
        span(S.DISPATCH, 25, 45, 0),
        span("serve.step", 60, 70),
    ])
    labels = [s.name if s else None
              for s in got.innermost([1, 7, 15, 22, 30, 55, 65, 80])]
    assert labels == ["serve.step", S.WAIT, S.DISPATCH, "serve.step",
                      S.DISPATCH, None, "serve.step", None]


@pytest.fixture(scope="module")
def v5e():
    return ProfileData.from_file(str(TRACE))


def test_v5e_trace_holds_every_dispatch_span(v5e):
    got = S.collect(v5e)
    assert got.count(S.DISPATCH) == 40
    assert {s.name for s in got.items} == set(PARENTS) - {"serve.compile"}
    for i, d in enumerate(got.items):
        if d.name == S.DISPATCH:
            assert d.args == {"dispatch": d.args["dispatch"], "rows": 32,
                              "extent": 32}
            kids = [s.name for s in got.items if s.parent == i]
            assert sorted(kids) == sorted([
                "serve.assemble", "serve.h2d", "serve.launch", S.WAIT,
                S.D2H, "serve.scatter", "serve.scatter"])
    assert got.host_seconds_per_dispatch() * 1e3 == pytest.approx(
        1.436, abs=1e-3)
    assert got.seconds_per_dispatch(S.D2H) * 1e3 == pytest.approx(
        0.411, abs=1e-3)


def test_v5e_device_clock_runs_early_and_idle_lies_under_spans(v5e):
    got = S.collect(v5e)
    # a run cannot end after the host's wait for it did: on the raw
    # clocks every one does, by ~1.9 ms
    assert S.device_offset_ns(v5e, got) * 1e-6 == pytest.approx(1.87,
                                                                 abs=0.01)
    out = S.summary(v5e)
    assert out["idle_s"] == pytest.approx(0.1112, abs=1e-4)
    assert out["idle_share_under_spans"] > 0.99
    split = out["idle_by_span_s"]
    assert sum(split.values()) == pytest.approx(out["idle_s"], rel=1e-9)
    assert max(split, key=split.get) == S.WAIT
    assert all(what.startswith(S.PREFIX) for what, _ in out["idle_gaps"])


def test_v5e_launch_names_and_scopes(v5e):
    s = trace.reduce(str(TRACE))
    stages = sorted(k for k in s.ops if "conv_stage" in k)
    assert stages == [f"%megakernel_conv_stage{i}.1" for i in (1, 2, 3)]
    assert all(s.ops[k].count == 40 for k in stages)
    assert s.op_seconds(r"megakernel_fc_trunk") == pytest.approx(
        0.00374, abs=1e-5)
    # the forward's named scopes do not reach the device's op events
    plane = v5e.find_plane_with_name("/device:TPU:0")
    texts = [ev.name for line in plane.lines if line.name == "XLA Ops"
             for ev in line.events]
    assert texts and not any("first_layer" in t or "stage1/" in t
                             for t in texts)
