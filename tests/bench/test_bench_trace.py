"""The reduction from a profiler trace to busy time, idle share, top
operations and idle gaps (``bench.tracefile``), and the metric readers
(``bench/metrics``) built on it.

``data/small.xplane.pb`` was recorded on a TPU v5e by
``record_trace.py``: three calls of a jitted loop of 400 matrix products
inside the harness's span names, with the host asleep for 0.25 s after
the second call and 0.02 s after the others (``data/small.json``).
"""
import json
from pathlib import Path

import pytest

from bench import spec, tracefile

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_sorts():
    assert tracefile.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_gaps_are_the_complement_inside_the_window():
    busy = tracefile.union([(2, 3), (5, 8)])
    assert tracefile.gaps(busy, 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert tracefile.gaps(tracefile.clip(busy, 2.5, 6), 2.5, 6) == [(3, 5)]


def _synthetic():
    ops = {"/device:TPU:0": [(10, 30, "fusion.1"), (20, 40, "conv.2"),
                             (70, 90, "fusion.1")],
           "/device:TPU:1": [(10, 90, "conv.2")]}
    host = [(0, 100, "bench.window"), (0, 50, "bench.call.0"),
            (50, 100, "bench.call.1"), (45, 68, "PjitFunction(step)")]
    return tracefile.Trace(ops, host)


def test_reduce_averages_busy_over_chips_and_names_gaps():
    out = tracefile.reduce(_synthetic(), (0, 100))
    # chip 0 busy 10..40 and 70..90 = 50 ns, chip 1 busy 80 ns
    assert out["busy_s"] == pytest.approx(65e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["idle_share"] == pytest.approx(0.35)
    assert out["n_devices"] == 2
    assert out["device_ops"][0] == ["conv.2", pytest.approx(50e-9)]
    # chip 0's gaps: 40..70 (host in call 1 at 55, inside the jit),
    # 0..10 and 90..100
    name, secs = out["idle_gaps"][0]
    assert name == "bench.call.1 / PjitFunction(step)"
    assert secs == pytest.approx(30e-9)
    assert [g[1] for g in out["idle_gaps"]] == pytest.approx(
        [30e-9, 10e-9, 10e-9])


def test_reduce_refuses_a_trace_without_devices():
    with pytest.raises(ValueError, match="no device plane"):
        tracefile.reduce(tracefile.Trace({}, []), (0, 1))


def _reader(name):
    return spec.load_module(spec.BENCH / "metrics" / f"{name}.py").read


def test_readers_from_a_reduced_trace():
    ctx = {"setup_s": 12.5, "window_s": 40.0, "node_rounds": 66, "chips": 1,
           "peak": {"bf16_flops_per_s": 197e12},
           "flops_per_node_round": 2.0e14,
           "trace": {"idle_share": 0.125, "busy_s": 35.0}}
    assert _reader("node_rounds_per_s")(ctx) == pytest.approx(1.65)
    # step_mfu divides by the chips' busy time, not by the host's window
    assert _reader("step_mfu")(ctx) == pytest.approx(
        100 * 66 * 2.0e14 / (35.0 * 197e12))
    two = dict(ctx, chips=2)
    assert _reader("step_mfu")(two) == pytest.approx(
        100 * 66 * 2.0e14 / (2 * 35.0 * 197e12))
    assert _reader("device_idle_share")(ctx) == pytest.approx(12.5)
    assert _reader("setup_s")(ctx) == 12.5
    untraced = dict(ctx, trace=None)
    assert _reader("step_mfu")(untraced) is None
    assert _reader("device_idle_share")(untraced) is None


@pytest.fixture(scope="module")
def recorded():
    return (tracefile.Trace.load(str(DATA / "small.xplane.pb")),
            json.loads((DATA / "small.json").read_text()))


def test_recorded_trace_reduces(recorded):
    trace, host = recorded
    window = trace.span("bench.window")
    assert window is not None
    out = tracefile.reduce(trace, window)
    assert out["n_devices"] == 1
    # the traced window is the host's, to within the tracer's own cost
    assert out["window_s"] == pytest.approx(host["host_window_s"], rel=0.05)
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_share"] < 1
    # the host slept 0.25 s in call 1: the longest gap, and named so
    name, secs = out["idle_gaps"][0]
    assert name.startswith("bench.call.1")
    assert 0.25 <= secs < 0.30
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    # the ops' time inside the window never exceeds what the chip was busy
    # times the ops that can overlap on one chip
    assert sum(s for _, s in out["device_ops"]) >= out["busy_s"] * 0.5
