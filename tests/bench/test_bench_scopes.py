"""Device self time by named scope, host time by program span and idle
time by span (``bench.scopes``), and the per-call numbers of a split
(``bench/split.py``).

``data/scoped.xplane.pb`` was recorded on a TPU v5e by
``record_scoped_trace.py``: twice, a ``local_train``-scoped scan of bf16
matrix products and then an ``eval``-scoped ``lax.map`` of them, each run
to completion and timed by the host (``data/scoped.json``).
"""
import json
import shutil
from pathlib import Path

import pytest

from bench import harness, scopes, spec, tracefile

TRACES = "/jax/core/compile/jaxpr_trace_duration"

DATA = Path(__file__).resolve().parent / "data"


def test_reads_tf_op_from_event_metadata():
    ops = scopes.read_tf_ops(str(DATA / "small.xplane.pb"))
    assert list(ops) == ["/device:TPU:0"]
    (name, path), = [(k, v) for k, v in ops["/device:TPU:0"].items()
                     if tracefile.op_name(k) == "convolution_tanh_fusion.2"]
    assert path == "jit(work)/while/body/closed_call/dot_general:"


def test_device_ops_are_the_trace_events_with_their_paths():
    path = str(DATA / "small.xplane.pb")
    ops = scopes.device_ops(path)
    trace = tracefile.Trace.load(path)
    assert [(a, b) for a, b, _ in ops["/device:TPU:0"]] == \
        [(a, b) for a, b, _ in trace.ops["/device:TPU:0"]]
    window = trace.span("bench.window")
    busy = scopes.scope_seconds(ops, window)
    assert busy == {"unscoped": pytest.approx(
        tracefile.reduce(trace, window)["busy_s"], abs=1e-12)}


@pytest.mark.parametrize("path, scope", [
    ("jit(f)/vmap()/while/body/closed_call/local_train/vmap()/while/"
     "body/closed_call/jvp()/dot_general", "local_train"),
    ("jit(f)/vmap(local_train)/vmap()/while", "local_train"),
    ("jit(f)/transpose(jvp(mix))/dot_general:", "mix"),
    ("jit(f)/eval/cond/branch_1_fun/while/body/closed_call/dot_general",
     "eval"),
    ("jit(f)/local_train/batch_gather/gather", "batch_gather"),
    ("jit(f)/while/body/add", "unscoped"),
    ("coeffs", "unscoped"),   # an argument's copy, named for the argument
    ("", "unscoped"),
])
def test_scope_of_takes_the_innermost_scope(path, scope):
    assert scopes.scope_of(path) == scope


def test_innermost_labels_nested_and_overlapping_events():
    events = [(0, 100, "loop"), (10, 20, "a"), (30, 50, "b"),
              (40, 60, "c"), (90, 120, "d")]
    assert scopes.innermost(events, 0, 110) == [
        (0, 10, "loop"), (10, 20, "a"), (20, 30, "loop"), (30, 40, "b"),
        (40, 60, "c"), (60, 90, "loop"), (90, 110, "d")]
    assert scopes.innermost([(5, 8, "x")], 10, 20) == []


def test_self_times_and_remainder_sum_to_busy():
    # a while (unscoped) holding two local_train products and one eval
    # product, an unscoped copy that overlaps the while's end, and an
    # op outside the window; chip 1 runs one mix op
    loop = "jit(f)/vmap()/while"
    train = "jit(f)/vmap()/while/body/closed_call/local_train/dot_general:"
    ev = "jit(f)/vmap()/while/body/closed_call/eval/cond/dot_general:"
    ops = {"/device:TPU:0": [(100, 700, loop), (150, 300, train),
                             (320, 520, train), (560, 640, ev),
                             (650, 800, "jit(f)/copy"), (900, 990, train)],
           "/device:TPU:1": [(200, 400, "jit(f)/mix/dot_general")]}
    out = scopes.scope_seconds(ops, (0, 850))
    # chip 0 busy 100..800; chip 1 busy 200..400; averaged over 2 chips
    assert out["local_train"] == pytest.approx((150 + 200) / 2 * 1e-9)
    assert out["eval"] == pytest.approx(80 / 2 * 1e-9)
    assert out["mix"] == pytest.approx(200 / 2 * 1e-9)
    # the loop's own time (50+20+40+10) and the copy's (150)
    assert out["unscoped"] == pytest.approx(270 / 2 * 1e-9)
    trace = tracefile.Trace({k: [(a, b, "op") for a, b, _ in v]
                             for k, v in ops.items()}, [])
    assert sum(out.values()) == pytest.approx(
        tracefile.reduce(trace, (0, 850))["busy_s"], rel=1e-12)


def test_scope_seconds_refuses_a_trace_without_devices():
    with pytest.raises(ValueError, match="no device plane"):
        scopes.scope_seconds({}, (0, 1))


HOST = [(0, 1000, "bench.window"), (0, 500, "bench.call.0"),
        (10, 200, "repro.sweep.prep"), (200, 260, "repro.engine.prepare"),
        (260, 450, "repro.engine.chunk"), (450, 470, "repro.engine.fetch"),
        (470, 495, "repro.sweep.summarize"), (500, 1000, "bench.call.1"),
        (510, 700, "repro.sweep.prep"), (700, 990, "repro.engine.chunk"),
        (300, 320, "PjitFunction(step)")]


def test_span_seconds_sum_each_name_inside_the_window():
    out = scopes.span_seconds(HOST, (0, 900))
    assert out == pytest.approx({
        "repro.sweep.prep": 380e-9, "repro.engine.prepare": 60e-9,
        "repro.engine.chunk": 390e-9, "repro.engine.fetch": 20e-9,
        "repro.sweep.summarize": 25e-9})


def test_idle_by_span_names_the_innermost_program_span():
    chip = [(0, 5, "op"), (300, 440, "op"), (720, 980, "op")]
    out = scopes.idle_by_span(HOST, chip, (0, 1000))
    assert out == pytest.approx({
        "outside": (10 - 5 + 500 - 495 + 510 - 500 + 1000 - 990) * 1e-9,
        "repro.sweep.prep": (190 + 190) * 1e-9,
        "repro.engine.prepare": 60e-9,
        "repro.engine.chunk": (40 + 10 + 20 + 10) * 1e-9,
        "repro.engine.fetch": 20e-9, "repro.sweep.summarize": 25e-9})
    # every idle instant of the window is named once
    busy = tracefile.union([(a, b) for a, b, _ in chip])
    idle = sum(b - a for a, b in tracefile.gaps(busy, 0, 1000)) * 1e-9
    assert sum(out.values()) == pytest.approx(idle)


PER_CALL = ("local_train_share", "eval_share", "entry_prep_s",
            "engine_host_s", "traces_per_call")


def _read(ctx: dict) -> dict:
    """The five per-call metrics as the harness reads them from ``ctx``."""
    cell = spec.Cell.load("gpt2s-1l.ba33.fedavg")
    metrics = harness.read_metrics(cell, ctx, trace=True)
    return {k: metrics[k]["value"] for k in PER_CALL if k in metrics}


def _ctx(reduced: dict, split: dict, calls: int, traces: int) -> dict:
    return dict(harness.trace_context(reduced, split), calls=calls,
                counts={TRACES: traces}, chips=1, peak=None,
                flops_per_node_round=None, node_rounds=66, window_s=40.0)


def test_per_call_numbers_of_a_split():
    split = {"calls": 2, "busy_s": 40.0,
             "scope_s": {"local_train": 37.0, "eval": 2.5, "unscoped": 0.5},
             "inclusive_s": {"local_train": 37.0, "eval": 2.5},
             "span_s": {"repro.sweep.prep": 1.8, "repro.engine.prepare": 0.4,
                        "repro.engine.chunk": 9.0}}
    ctx = _ctx({"busy_s": 40.0, "idle_share": 0.1}, split, 2, 1230)
    assert _read(ctx) == pytest.approx({
        "local_train_share": 92.5, "eval_share": 6.25, "entry_prep_s": 0.9,
        "engine_host_s": 4.7, "traces_per_call": 615.0})
    # untraced, the readers of the trace find nothing to read
    untraced = dict(ctx, **harness.trace_context(None, None))
    assert _read(untraced) == {"traces_per_call": 615.0}


@pytest.fixture(scope="module")
def recorded():
    path = str(DATA / "scoped.xplane.pb")
    return path, json.loads((DATA / "scoped.json").read_text())


def test_recorded_scopes_match_the_host_timing(recorded):
    path, host = recorded
    trace = tracefile.Trace.load(path)
    window = trace.span("bench.window")
    out = scopes.split(trace, scopes.device_ops(path), window)
    assert out["calls"] == 2
    # self times and the unscoped rest are the busy time step_mfu reads
    assert out["busy_s"] == pytest.approx(
        tracefile.reduce(trace, window)["busy_s"], abs=1e-9)
    total = host["train_s"] + host["eval_s"]
    share = {k: 100 * v / out["busy_s"] for k, v in out["scope_s"].items()}
    assert share["local_train"] == pytest.approx(
        100 * host["train_s"] / total, abs=2.0)
    assert share["eval"] == pytest.approx(100 * host["eval_s"] / total,
                                          abs=2.0)


def test_harness_ctx_holds_the_recorded_split(recorded, tmp_path, monkeypatch):
    """The harness reads the traced window once, removes it, and gives the
    readers the scope times and spans that ``scopes.split`` gives; the
    readers' shares are inclusive scope time over the busy time."""
    path, _ = recorded
    traced = tmp_path / "trace" / "plugins" / "profile" / "1"
    traced.mkdir(parents=True)
    shutil.copy(path, traced / "host.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    reduced, split = harness.read_trace(scopes.SCOPES)
    assert not (tmp_path / "trace").exists()
    trace = tracefile.Trace.load(path)
    window = trace.span("bench.window")
    want = scopes.split(trace, scopes.device_ops(path), window)
    assert reduced == tracefile.reduce(trace, window)
    assert split == want
    ctx = _ctx(reduced, split, want["calls"], 30)
    assert ctx["scopes"] == {"self": want["scope_s"],
                             "inclusive": want["inclusive_s"]}
    assert ctx["spans"] == want["span_s"] == {}
    busy = reduced["busy_s"]
    # nothing nests in the recording's two scopes
    assert want["inclusive_s"] == {k: v for k, v in want["scope_s"].items()
                                   if k != "unscoped"}
    # no program spans in the recording: their readers find nothing
    assert _read(ctx) == pytest.approx({
        "local_train_share": 100 * want["inclusive_s"]["local_train"] / busy,
        "eval_share": 100 * want["inclusive_s"]["eval"] / busy,
        "traces_per_call": 15.0})


LOOP = "jit(f)/vmap()/while"
TRAIN = "jit(f)/vmap()/while/body/closed_call/local_train/dot_general:"
EVAL = "jit(f)/vmap()/while/body/closed_call/eval/cond/dot_general:"


def _synthetic(ops: dict) -> tracefile.Trace:
    return tracefile.Trace({k: [(a, b, "op") for a, b, _ in v]
                            for k, v in ops.items()}, HOST)


def test_readers_of_a_synthetic_split():
    """Calls and program spans the recording lacks, on synthetic events:
    two calls, each with its spans (``HOST``)."""
    ops = {"/device:TPU:0": [(100, 700, LOOP), (150, 300, TRAIN),
                             (320, 520, TRAIN), (560, 640, EVAL)]}
    trace = _synthetic(ops)
    split = scopes.split(trace, ops, (0, 1000))
    reduced = tracefile.reduce(trace, (0, 1000))
    assert split["calls"] == 2 and reduced["busy_s"] == pytest.approx(600e-9)
    assert _read(_ctx(reduced, split, split["calls"], 1230)) == pytest.approx({
        "local_train_share": 100 * 350 / 600, "eval_share": 100 * 80 / 600,
        "entry_prep_s": 380e-9 / 2,
        "engine_host_s": (60 + 190 + 290) * 1e-9 / 2,
        "traces_per_call": 615.0})


def test_a_declared_scope_nested_in_local_train():
    """A scope that only a model file declares (``expert``) owns its ops'
    self time; ``local_train`` keeps its inclusive time."""
    expert = ("jit(f)/vmap()/while/body/closed_call/local_train/"
              "vmap(expert)/dot_general:")
    ops = {"/device:TPU:0": [(100, 700, LOOP), (150, 300, TRAIN),
                             (320, 520, expert), (560, 640, EVAL)]}
    trace = _synthetic(ops)
    plain = scopes.split(trace, ops, (0, 1000))
    named = scopes.split(trace, ops, (0, 1000),
                         scopes.SCOPES + ("expert",))
    assert "expert" not in plain["scope_s"]
    assert plain["scope_s"]["local_train"] == pytest.approx(350e-9)
    assert named["scope_s"]["expert"] == pytest.approx(200e-9)
    assert named["scope_s"]["local_train"] == pytest.approx(150e-9)
    assert named["inclusive_s"]["expert"] == pytest.approx(200e-9)
    for split in (plain, named):
        assert split["inclusive_s"]["local_train"] == pytest.approx(350e-9)
        assert split["busy_s"] == pytest.approx(600e-9)
    assert scopes.scopes_of(expert, scopes.SCOPES + ("expert",)) == [
        "local_train", "expert"]


@pytest.mark.parametrize("name", sorted(p.stem for p in (
    spec.BENCH / "configs").glob("*.json")))
def test_declared_scopes_of_the_benchmark_models(name):
    """None of the benchmark's own models declares a scope of its own."""
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    assert scopes.declared(cfg) == scopes.SCOPES
