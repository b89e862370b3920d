"""CPU tests of the readers of the program's wall spans: both clock
alignments, the quiet-call filter, idle attribution, every reader on a
hand-built run, and one traced run at a tiny size."""
import importlib.util
import json
import pathlib
import time

import pytest

from chipbench import spans, xtrace
from chipbench.harness import RunRecord
from repro.obs.trace import Span
from test_chipbench_run import (CPU_PEAKS, SEED, TINY_LIMIT,  # noqa: F401
                                _tiny_root, jax_config)

HERE = pathlib.Path(__file__).resolve().parent
PROGRAM = 100.0     # the program's clock reads the harness's + this
PROFILER = 10.0     # the profiler's clock reads the harness's + this
READERS = ("fetch_ms", "commit_ms", "upload_gb_per_s", "decode_step_ms",
           "token_gap_p99_ms", "idle_unattributed.prefill",
           "idle_unattributed.decode")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(track, name, t0, t1, **args):
    """A span at harness times ``t0``-``t1``, on the program's clock."""
    return Span(track, name, t0 + PROGRAM, t1 + PROGRAM, "engine", args)


def _serves(calls, lag=0.0):
    return [_span("engine/wall", "serve", a + lag, b, requests=n)
            for a, b, n in calls]


def _run(calls, program_spans, profiled=(), trace=None):
    rec = RunRecord(cell=type("C", (), {"config": {}})(), setup_s=1.0,
                    requests=[], calls=calls, spans=program_spans, peaks={},
                    profiled=profiled)
    if trace is not None:
        rec.trace, rec.offset = trace, PROFILER
        rec.traced = tuple(t + PROFILER for t in profiled)
    return rec


# three calls; the second overlaps the trace
CALLS = [(0.0, 1.0, 1), (2.0, 3.0, 1), (4.0, 5.0, 1)]


def _requests():
    """r0 and r2 in quiet calls, r1 in the traced one; each fetches,
    uploads, slices, computes, commits, and decodes two tokens."""
    out = []
    for k, r in enumerate(("r0", "r1", "r2")):
        a = CALLS[k][0]
        w = r + "/wall"
        out += [_span(w, "fetch", a + 0.01, a + 0.01 + 0.02 * (k + 1),
                      objects=4, bytes=1000),
                _span(w, "upload", a + 0.1, a + 0.15, layer=0, bytes=1e8),
                _span(w, "compute", a + 0.12, a + 0.3, layer=0),
                _span(w, "slice", a + 0.15, a + 0.15 + 0.01 * (k + 1),
                      layer=0),
                _span(w, "final", a + 0.3, a + 0.4),
                _span(w, "commit", a + 0.4, a + 0.4 + 0.1 * (k + 1),
                      chunks=2, bytes=500),
                _span("engine/wall", "decode_step", a + 0.6, a + 0.65,
                      req_ids=[r], after="PREFILL_DONE"),
                _span("engine/wall", "decode_step", a + 0.65, a + 0.9,
                      req_ids=[r], after="drain"),
                # the virtual clock's spans of the same names are not read
                Span(r, "fetch", 0.0, 50.0), Span(r, "compute", 0.0, 50.0)]
    return out


def test_harness_clock_and_quiet_calls():
    run = _run(CALLS, _serves(CALLS, lag=1e-4) + _requests(),
               profiled=(1.5, 3.5))
    assert spans.harness_offset(run) == pytest.approx(-PROGRAM - 1e-4)
    assert sorted(s.track for s in spans.quiet(run, "fetch")) == \
        ["r0/wall", "r2/wall"]
    assert spans.per_request(run, "commit") == pytest.approx(
        {"r0/wall": 0.1, "r2/wall": 0.3})
    # one call without its serve span: the clocks cannot be tied
    assert spans.harness_offset(_run(CALLS, _serves(CALLS)[1:])) is None
    assert spans.quiet(_run(CALLS, _serves(CALLS)[1:]), "fetch") is None


def test_every_reader_on_a_hand_built_run():
    run = _run(CALLS, _serves(CALLS) + _requests(), profiled=(1.5, 3.5))
    read = {n: _reader(n).read(run) for n in READERS}
    assert read["fetch_ms"] == pytest.approx(20.0)      # r0 20, r2 60
    assert read["commit_ms"] == pytest.approx(100.0)    # r0 100, r2 300
    assert read["upload_gb_per_s"] == pytest.approx(2e8 / 0.1 / 1e9)
    assert read["decode_step_ms"] == pytest.approx(50.0)   # 50, 250 x2
    # gaps: final -> step 1 is 0.25 s, step 1 -> step 2 is 0.25 s
    assert read["token_gap_p99_ms"] == pytest.approx(250.0)
    # no trace: the idle readers have nothing to read
    assert read["idle_unattributed.prefill"] is None
    assert read["idle_unattributed.decode"] is None


def test_readers_read_nothing_from_a_program_without_wall_serve_spans():
    """A program that emits no ``engine/wall`` spans (or no spans at all)
    leaves every new reader empty, and none of them raises."""
    only_req = [s for s in _requests() if s.track != "engine/wall"]
    trace = xtrace.from_events({"/device:TPU:0": [("op", 12.0, 12.5)]},
                               [("chipbench.serve", 12.0, 13.0)])
    for program_spans in ([], only_req):
        run = _run(CALLS, program_spans, profiled=(1.5, 3.5), trace=trace)
        assert {n: _reader(n).read(run) for n in READERS} == \
            dict.fromkeys(READERS)


def _traced(program_spans, host, ops):
    return _run([(0.0, 1.0, 1)], program_spans, profiled=(0.0, 1.0),
                trace=xtrace.from_events({"/device:TPU:0": ops}, host))


def test_profiler_clock_from_serve_annotations():
    """The serve span's start, 0.2 ms from its annotation's: the offset is
    the annotation's clock; a spread of 2 ms is refused."""
    serve = _serves([(0.0, 1.0, 1)])
    true = PROFILER - PROGRAM + 2e-4
    ann = [("chipbench.serve", PROGRAM + true, PROGRAM + 1.0 + true)]
    run = _traced(serve, ann, [("op", 10.0, 10.5)])
    assert spans.profiler_offset(run) == pytest.approx(true)
    # two calls whose annotations lie 2 ms apart in their offsets
    calls = [(0.0, 0.4, 1), (0.5, 1.0, 1)]
    anns = [("chipbench.serve", a + PROFILER, b + PROFILER)
            for a, b, _ in calls]
    anns[1] = ("chipbench.serve", anns[1][1] + 2e-3, anns[1][2])
    run = _run(calls, _serves(calls), profiled=(0.0, 1.0),
               trace=xtrace.from_events({"/device:TPU:0": []}, anns))
    assert spans.profiler_offset(run) is None
    anns[1] = ("chipbench.serve", 0.5 + PROFILER + 9e-4, 1.0 + PROFILER)
    run.trace = xtrace.from_events({"/device:TPU:0": []}, anns)
    assert spans.profiler_offset(run) == pytest.approx(
        PROFILER - PROGRAM + 4.5e-4)


def test_profiler_clock_from_decode_steps():
    """A call that outlasts the trace leaves no serve annotation in it; the
    Python tracer's ``step`` events pair with the ``decode_step`` spans.
    A step call that emitted no span (an empty batch) pairs with nothing."""
    steps = [_span("engine/wall", "decode_step", 0.1 * i, 0.1 * i + 0.06,
                   req_ids=["r"], after="WIRE") for i in range(1, 9)]
    true = PROFILER - PROGRAM - 3e-5
    host = [(f"$batching.py:{97 if i % 2 else 98} step",
             s.t0 + true + 1e-5 * (i % 3), s.t1 + true)
            for i, s in enumerate(steps)]
    host.append(("$batching.py:97 step", 0.95 + PROFILER, 0.96 + PROFILER))
    host.append(("$engine.py:10 other", 0.5 + PROFILER, 0.51 + PROFILER))
    run = _traced(_serves([(0.0, 1.0, 1)]) + steps, host, [])
    assert spans.profiler_offset(run) == pytest.approx(true + 1e-5)
    jittered = [(n, a + 2e-3 * (i == 3), b) for i, (n, a, b) in
                enumerate(host)]
    run.trace = xtrace.from_events({"/device:TPU:0": []}, jittered)
    assert spans.profiler_offset(run) is None


def test_idle_attribution_charges_the_innermost_span():
    """Gaps 0.1-0.3 and 0.5-0.9 s into the call: the first half under
    ``fetch``, the second under ``compute`` with a ``slice`` inside it; what
    no span covers is unattributed, whatever ``serve`` covers."""
    program = _serves([(0.0, 1.0, 1)]) + [
        _span("r/wall", "fetch", 0.05, 0.25),
        _span("r/wall", "compute", 0.5, 0.8),
        _span("r/wall", "slice", 0.55, 0.6),
        Span("r", "compute", 0.0, 50.0)]
    ann = [("chipbench.serve", PROFILER, PROFILER + 1.0)]
    ops = [("op", PROFILER + a, PROFILER + b)
           for a, b in ((0.0, 0.1), (0.3, 0.5), (0.9, 1.0))]
    run = _traced(program, ann, ops)
    idle = spans.idle_by_span(run)
    assert idle == pytest.approx({"fetch": 0.15, spans.NO_SPAN: 0.15,
                                  "compute": 0.25, "slice": 0.05})
    for name in ("idle_unattributed.prefill", "idle_unattributed.decode"):
        assert _reader(name).read(run) == pytest.approx(25.0)
    assert spans.charge([(0.0, 1.0)], []) == {spans.NO_SPAN: 1.0}
    assert spans.charge([], [(0.0, 1.0, "x")]) == {}


def test_traced_run_at_tiny_size_reads_the_program_spans(tmp_path,
                                                          jax_config):
    """One traced run of each tiny cell: the fetch, commit, decode-step and
    token-gap readers find the program's spans in the quiet calls."""
    from chipbench import harness

    root = _tiny_root(tmp_path, TINY_LIMIT)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, cell, moves in (
            ("fetch_ms", "tiny.open", "ttft_p50_ms"),
            ("commit_ms", "tiny.open", "ttft_p50_ms"),
            ("decode_step_ms", "tiny.closed", "output_tokens_per_s"),
            ("token_gap_p99_ms", "tiny.closed", "output_tokens_per_s")):
        bench["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "test", "moves": moves,
             "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness.peaks_mod, "peaks_for", lambda kind: CPU_PEAKS)
        for cell, names in (("tiny.open", ("fetch_ms", "commit_ms")),
                            ("tiny.closed", ("decode_step_ms",
                                             "token_gap_p99_ms"))):
            out = harness.run(root, cell, SEED, 2.0, True,
                              t_start=time.perf_counter(), check_device=False)
            assert out["correct"], out["checks"]
            for name in names:
                assert out["metrics"][name]["value"] > 0, (cell, name)
