"""The program's host spans in a profiler trace, on the CPU: one tiny
``run_sweep_cells`` call of two rounds in chunks of one, traced inside the
harness's ``bench.call.0`` span and read back by ``bench.tracefile``."""
import jax
import pytest
from jax.profiler import TraceAnnotation

from bench import scopes, tracefile

SPANS = ("repro.sweep.prep", "repro.engine.prepare", "repro.engine.chunk",
         "repro.engine.fetch", "repro.sweep.summarize")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from benchmarks.common import BenchScale, SweepCell, run_sweep_cells
    from repro.core.topology import barabasi_albert

    cells = [SweepCell("mnist", barabasi_albert(4, 2, seed=3), "degree",
                       seed=3)]
    scale = BenchScale(n_train=160, n_test=40, rounds=2, local_epochs=1,
                       batch=4, steps_per_epoch=2, eval_every=1, eval_n=8)
    call = lambda: run_sweep_cells(cells, scale=scale, chunk_rounds=1)
    call()   # compiled before the trace, as the harness's warm-up does
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("bench.call.0"):
                call()
    finally:
        jax.profiler.stop_trace()
    return tracefile.Trace.load(tracefile.find_xplane(str(out)))


def test_spans_nest_in_the_call_in_order(traced):
    call = traced.span("bench.call.0")
    assert call is not None
    spans = sorted((a, b, n) for a, b, n in traced.host if n in SPANS)
    assert {n for _, _, n in spans} == set(SPANS)
    assert all(call[0] <= a and b <= call[1] for a, b, _ in spans)
    first = {}
    for a, _, n in spans:
        first.setdefault(n, a)
    assert sorted(first, key=first.get) == list(SPANS)
    # the host's spans do not overlap: each is the host's one layer
    assert all(b0 <= a1 for (_, b0, _), (a1, _, _) in zip(spans, spans[1:]))


def test_one_chunk_span_per_chunk(traced):
    names = [n for _, _, n in traced.host]
    assert names.count("repro.engine.chunk") == 2
    assert names.count("repro.engine.fetch") == 2
    assert names.count("repro.sweep.prep") == 1


def test_span_seconds_read_the_call(traced):
    window = traced.span("bench.window")
    out = scopes.span_seconds(traced.host, window)
    assert set(out) == set(SPANS)
    assert sum(out.values()) <= (window[1] - window[0]) * 1e-9
