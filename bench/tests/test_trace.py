"""The trace reduction, on a recorded TPU v5e trace: 0.2 s of the backlog
cell's window (40 dispatches of 32 images, engine ``megakernel``),
recorded with ``bench/record_trace.py``."""

import pathlib

import pytest
from jax.profiler import ProfileData

from bench import trace

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "v5e_backlog.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(TRACE))


def _device_events():
    data = ProfileData.from_file(str(TRACE))
    plane = data.find_plane_with_name("/device:TPU:0")
    (ops,) = [ln for ln in plane.lines if ln.name == "XLA Ops"]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in ops.events]


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    assert trace._union([]) == []


def test_window_and_busy_time(summary):
    assert summary.chips == 1
    assert summary.window_s == pytest.approx(0.238, abs=0.002)
    # busy is the union of the op intervals inside the window
    assert 0 < summary.busy_s < summary.window_s
    assert summary.busy_s == pytest.approx(0.1267, abs=0.0005)
    idle = 1 - summary.busy_s / summary.window_s
    assert 0.4 < idle < 0.5


def test_busy_time_matches_the_events(summary):
    events = _device_events()
    merged = trace._union([(s, e) for _, s, e in events])
    union = sum(e - s for s, e in merged) * 1e-9
    # the window (the bench.window span) covers all 40 dispatches
    assert summary.busy_s == pytest.approx(union, rel=1e-3)
    total = sum(o.seconds for o in summary.ops.values())
    assert total >= summary.busy_s * (1 - 1e-9)


def test_kernels_found_by_name(summary):
    stages = [k for k in summary.ops if "megakernel_conv_stage" in k]
    assert len(stages) == 3
    assert all(summary.ops[k].count == 40 for k in stages)
    conv = summary.op_seconds(r"conv_stage")
    assert conv == pytest.approx(sum(summary.ops[k].seconds for k in stages))
    assert conv == pytest.approx(0.0800, abs=0.0005)
    # the FC trunk's launch, a Pallas custom call of another name
    assert summary.op_seconds(r"megakernel_chain") == pytest.approx(0.00375,
                                                                      abs=1e-4)
    assert summary.op_seconds(r"no_such_kernel") is None


def test_breakdown_lists_the_longest(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0] == "%megakernel_conv_stage.5"
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    idle = sum(s for s, _ in summary.gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)


def test_gaps_are_named_by_the_host_span_over_them():
    s0 = trace.reduce(str(TRACE))
    # place one host span over the whole window: every gap falls in it
    s1 = trace.reduce(str(TRACE), host_spans=[("engine.step", 10.0, 20.0)],
                      window_perf=(10.0, 10.0 + s0.window_s))
    assert {what for _, what in s1.gaps} == {"engine.step"}
    assert {what for _, what in s0.gaps} == {"no host span"}
