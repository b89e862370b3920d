"""One run of one cell: set-up, warm-up, the measured window, the check
against the reference, and the metric readers.

``run`` is the whole run; ``run.py`` adds only the look for a chip and
the printing.  A test may call ``run`` without the look for a chip.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from . import peaks as peaks_mod
from . import stats, xtrace
from .layout import Cell, Layout
from .reference import logit_gaps
from .system import build, request
from .traffic import Request, Traffic
from .weights import make_params

SERVE_ANNOTATION = "chipbench.serve"
MARK_ANNOTATION = "chipbench.mark"
PROFILE_AT = 0.4      # the traced sub-window starts this far into the window
PROFILE_S = 3.0       # ... ends with the first call that ends after this
PROFILE_MAX_S = 6.0   # ... or, inside a longer call, after this


class Profile:
    """The traced sub-window of a ``--trace 1`` run.  It starts at the start
    of a serve() call and stops at the end of the first call that ends
    ``PROFILE_S`` later, or from a timer ``PROFILE_MAX_S`` after the start,
    inside a long call.  A ``chipbench.mark`` annotation at the start ties
    the profiler's clock to the harness's.  The device is recorded only
    from some 100 ms later, so the readers take the trace from its first
    device operation on (``RunRecord.place_trace``)."""

    def __init__(self, log_dir: str, t0: float) -> None:
        import threading

        self.dir, self.t0 = log_dir, t0
        self.start_s = self.stop_s = self.mark_s = None
        self._lock = threading.Lock()
        self._timer = threading.Timer(PROFILE_MAX_S, self.stop)
        self._timer.daemon = True

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self.start_s = time.perf_counter() - self.t0
        with jax.profiler.TraceAnnotation(MARK_ANNOTATION):
            self.mark_s = time.perf_counter() - self.t0
        self._timer.start()

    def stop(self) -> None:
        import jax

        with self._lock:
            if self.start_s is not None and self.stop_s is None:
                # read before the call: stop_trace then writes the trace
                # out, for tens of seconds with the Python tracer on
                self.stop_s = time.perf_counter() - self.t0
                jax.profiler.stop_trace()

    def close(self) -> None:
        self._timer.cancel()
        self.stop()


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, from JAX's
    backend-compile event, which wraps both (after
    ``chip_smoke.CompileClock``).  One inside the window means a shape that
    set-up did not warm."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.names.append(str(kwargs.get("fun_name", "?")))

    @property
    def programs(self) -> int:
        return len(self.names)


@dataclasses.dataclass
class RunRecord:
    """What the metric readers read.  Times are seconds from the window's
    start on the host clock; ``trace`` times are the profiler's."""

    cell: Cell
    setup_s: float
    requests: list          # one dict per request due in the window
    calls: list             # (start, end, n_requests) of each serve() call
    spans: list             # repro.obs.trace.Span records (traced runs)
    peaks: dict
    trace: Optional[xtrace.Trace] = None
    # (start, stop) of the trace on the profiler's clock, from the first
    # device operation recorded: the device is recorded only some 100 ms
    # after the trace starts
    traced: tuple = ()
    offset: float = 0.0     # profiler clock - harness clock
    profiled: tuple = ()    # (start, stop) of the trace, harness clock

    @property
    def conf(self) -> dict:
        return self.cell.config

    def profiled_calls(self) -> list:
        """Indices of the calls that lie wholly inside the trace."""
        if not self.traced:
            return []
        lo, hi = (t - self.offset for t in self.traced)
        return [i for i, (a, b, _) in enumerate(self.calls)
                if a >= lo and b <= hi]

    def quiet_calls(self) -> set:
        """Indices of the calls that do not overlap the trace.  The host
        clock's per-layer readers read only these: the profiler's Python
        tracer slows the host while it records."""
        lo, hi = self.profiled or (float("inf"), float("inf"))
        return {i for i, (a, b, _) in enumerate(self.calls)
                if b < lo or a > hi}

    def quiet_requests(self) -> list:
        """The requests whose life, from due to served, misses the trace."""
        lo, hi = self.profiled or (float("inf"), float("inf"))
        return [r for r in self.requests if r["end"] < lo or r["due"] > hi]

    def place_trace(self, trace: xtrace.Trace, start_s: float, stop_s: float,
                    mark_s: float) -> None:
        """Attach the trace of the harness-clock stretch ``start_s`` to
        ``stop_s``, tying the clocks by the mark annotation made at
        ``mark_s``."""
        self.trace, self.profiled = trace, (start_s, stop_s)
        self.offset = trace.annotations(MARK_ANNOTATION)[0][0] - mark_s
        start = start_s + self.offset
        recorded = xtrace.recorded_from(trace)
        self.traced = (start if recorded is None else max(start, recorded),
                       stop_s + self.offset)

    def busy_windows(self) -> list:
        """The serve() calls inside the trace, on the profiler's clock."""
        if not self.traced:
            return []
        return stats.clip([(a + self.offset, b + self.offset)
                           for a, b, _ in self.calls], [self.traced])


def _program_files() -> frozenset:
    """Basenames of the program's Python files, to label idle gaps by."""
    import pathlib

    import repro
    return frozenset(p.name for p in
                     pathlib.Path(list(repro.__path__)[0]).rglob("*.py"))


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def _serve(engine, reqs, arrivals, traffic):
    import jax

    batch = [request(f"r{r.index}", traffic.prompt(r), a, r.max_new_tokens)
             for r, a in zip(reqs, arrivals)]
    with jax.profiler.TraceAnnotation(SERVE_ANNOTATION):
        return engine.serve(batch)


def commit_documents(system, traffic: Traffic, section: int) -> None:
    """Commit every document through ``serve()`` in sections of ``section``
    tokens: the first a cold prefill, each later one a warm hit on what
    precedes it."""
    for i, doc in enumerate(traffic.documents):
        if len(doc) % section:
            raise ValueError(f"document of {len(doc)} tokens is not whole "
                             f"sections of {section}")
        for end in range(section, len(doc) + 1, section):
            system.engine.serve([request(f"doc{i}.{end}", doc[:end], 0.0, 0)])


def warm_up(system, traffic: Traffic) -> None:
    """Compile every shape the window uses: each suffix bucket, and for a
    mix that decodes, more requests than slots so that admission,
    queueing and the batched step all run."""
    buckets = traffic.bucket_sizes()
    decodes = max(traffic.output_sizes) > 0
    n = (len(buckets) + int(traffic.engine.get("num_slots", 1))
         if decodes else len(buckets))
    rng = np.random.default_rng(0)
    reqs = [Request(-1 - i, i % len(traffic.documents),
                    rng.integers(0, traffic.vocab, buckets[i % len(buckets)],
                                 dtype=np.int32), 2 if decodes else 0, 0.0)
            for i in range(n)]
    _serve(system.engine, reqs, [0.0] * n, traffic)


def prepare(cell: Cell, seed: int, tracer=None):
    """Set-up: the mix from the seed, the system with weights from the
    seed, the documents committed, the cell's shapes warmed.  Returns
    (traffic, system)."""
    t = time.perf_counter()
    conf = cell.config
    traffic = Traffic(cell.traffic, conf["vocab_size"], seed)
    system = build(cell, lambda m: make_params(m, seed), traffic.engine,
                   tracer)
    built = time.perf_counter()
    commit_documents(system, traffic,
                     int(conf["serving"]["commit_section_tokens"]))
    committed = time.perf_counter()
    warm_up(system, traffic)
    _log(f"[setup] build_s={built - t} commit_s={committed - built} "
         f"warm_up_s={time.perf_counter() - committed}")
    return traffic, system


def drive(system, traffic: Traffic, seconds: float, profile_dir=None):
    """The measured window.  Open loop: whenever the harness is free, every
    request that is due goes into one ``serve()`` call, with its due
    offset as arrival; requests due before the window closes are all
    served.  Closed loop: calls of ``batch`` requests back to back while
    the window is open.  Returns (requests, calls, profile or None)."""
    clock = time.perf_counter
    sched = traffic.requests()
    nxt = next(sched)
    records, calls = [], []
    t0 = clock()
    prof = Profile(profile_dir, t0) if profile_dir is not None else None
    try:
        while True:
            now = clock() - t0
            if traffic.loop == "open":
                if nxt.due_s >= seconds:
                    break
                if nxt.due_s > now:
                    time.sleep(nxt.due_s - now)
                    continue
                batch = []
                while nxt.due_s <= now and nxt.due_s < seconds:
                    batch.append(nxt)
                    nxt = next(sched)
                arrivals = [r.due_s for r in batch]
            else:
                if now >= seconds:
                    break
                batch = [nxt] + [next(sched) for _ in
                                 range(int(traffic.mix["batch"]) - 1)]
                nxt = next(sched)
                arrivals = [0.0] * len(batch)
            if (prof is not None and prof.start_s is None
                    and now >= PROFILE_AT * seconds):
                prof.start()
            start = clock() - t0
            results = _serve(system.engine, batch, arrivals, traffic)
            end = clock() - t0
            call = len(calls)
            calls.append((start, end, len(batch)))
            if (prof is not None and prof.start_s is not None
                    and end >= prof.start_s + PROFILE_S):
                prof.stop()
            for r in batch:
                records.append(_record(r, results.get(f"r{r.index}"),
                                       traffic, start, end, call))
    finally:
        if prof is not None:
            prof.close()
    return records, calls, prof


def _record(r, res, traffic: Traffic, start: float, end: float,
            call: int) -> dict:
    rec = {"index": r.index, "doc": r.doc, "tokens": r.suffix,
           "suffix": len(r.suffix), "max_new": r.max_new_tokens,
           "due": r.due_s if traffic.loop == "open" else start,
           "start": start, "end": end, "call": call,
           "served": None, "matched": -1, "finite": False}
    if res is not None:
        lg = np.asarray(res.logits, np.float32)[:traffic.vocab]
        rec.update(matched=int(res.matched_tokens),
                   finite=bool(np.all(np.isfinite(lg))),
                   served=([int(np.argmax(lg))] if r.max_new_tokens == 0
                           else [int(t) for t in res.new_tokens]))
    return rec


def count_failed(traffic: Traffic, records: list) -> int:
    """Requests with no result, not the length asked for, a non-finite
    logit, or a prefix hit other than their whole document."""
    doc_len = len(traffic.documents[0])
    return sum(1 for r in records
               if r["served"] is None or len(r["served"]) != max(1, r["max_new"])
               or not r["finite"] or r["matched"] != doc_len)


def sample(records: list, served_tokens: int, seed: int) -> list:
    """Requests to compare, drawn from the seed: the one with the most
    served tokens, then others in a seeded order until ``served_tokens``."""
    good = [r for r in records if r["served"] is not None]
    if not good:
        return []
    first = max(good, key=lambda r: len(r["served"]))
    rng = np.random.default_rng([int(seed), 3])
    out, n = [], 0
    for r in [first] + [good[i] for i in rng.permutation(len(good))
                        if good[i] is not first]:
        if n >= served_tokens:
            break
        out.append(r)
        n += len(r["served"])
    return out


# what a limits file may bound, over the gaps of every compared token
GAP_STATS = {"max_logit_gap": np.max, "mean_logit_gap": np.mean}


def read_limits(layout: Layout, workload: str) -> dict:
    """The cell's ``limits/<cell>.json``: ``sample_served_tokens`` and a
    limit on one statistic of ``GAP_STATS`` or more, and no other key.  A
    file that bounds no gap, or names a key that nothing reads, is refused
    at set-up: ``correct`` would not judge the logits."""
    path = layout.find("limits", workload + ".json")
    limits = json.loads(path.read_text())
    unknown = sorted(set(limits) - set(GAP_STATS) - {"sample_served_tokens"})
    if unknown or "sample_served_tokens" not in limits or not (
            set(GAP_STATS) & set(limits)):
        raise ValueError(
            f"{path} has to give sample_served_tokens and bound one of "
            f"{sorted(GAP_STATS)} or more, and nothing else; it gives "
            f"{sorted(limits)}" + (f" (unknown: {unknown})" if unknown
                                   else ""))
    return limits


def compare(params, cell: Cell, traffic: Traffic, chosen: list,
            control: bool = False) -> dict:
    """The gap by which each served token's reference logit lies below the
    reference's best, over every served token of ``chosen``.  Returns
    ``{"program": gaps}``, and with ``control`` also ``"control"``: the
    gaps of the fp8 reference put in the program's place, read at the same
    positions of the same prompts and served tokens (the tokens it puts
    first there)."""
    doc_len = len(traffic.documents[0])
    conf, Reference = cell.config, cell.arch.Reference
    ref = Reference(params, conf)
    low = Reference(params, conf, quant="fp8") if control else None
    out = {side: [np.zeros(0, np.float32)]
           for side in (("program", "control") if control else ("program",))}

    for doc in sorted({r["doc"] for r in chosen}):
        pk = ref.prefix_kv(traffic.documents[doc])
        pk_low = low.prefix_kv(traffic.documents[doc]) if control else None
        for r in (c for c in chosen if c["doc"] == doc):
            fed = np.concatenate([r["tokens"], r["served"][:-1]]).astype(
                np.int32)
            at = (doc_len, fed, r["suffix"] - 1, len(r["served"]))
            lg = ref.logits(pk, *at)
            out["program"].append(logit_gaps(lg, r["served"]))
            if control:
                first = np.asarray(low.logits(pk_low, *at).argmax(-1))
                out["control"].append(logit_gaps(lg, first))
        del pk, pk_low
    return {side: np.concatenate(g) for side, g in out.items()}


def gap_checks(gaps: np.ndarray, limits: dict) -> dict:
    """Each statistic of ``GAP_STATS`` that ``limits`` bounds, read over
    ``gaps``, beside its limit."""
    return {name: {"value": float(stat(gaps)) if len(gaps) else 0.0,
                   "limit": limits[name]}
            for name, stat in GAP_STATS.items() if name in limits}


def check(params, cell: Cell, traffic: Traffic, records: list, limits: dict,
          seed: int, control: bool = False):
    """(failed requests, each compared number with its limit, and with
    ``control`` the same numbers with the fp8 control in the program's
    place, else None)."""
    chosen = sample(records, limits["sample_served_tokens"], seed)
    read = compare(params, cell, traffic, chosen, control)
    good = [r for r in records if r["served"] is not None]

    def checks(side):
        return dict(gap_checks(read[side], limits), served_tokens_compared={
            "value": sum(len(r["served"]) for r in chosen),
            "limit": min(limits["sample_served_tokens"],
                         sum(len(r["served"]) for r in good))})
    return (count_failed(traffic, records), checks("program"),
            checks("control") if control else None)


def compared_ok(checks: dict) -> bool:
    return all(c["value"] >= c["limit"] if name == "served_tokens_compared"
               else c["value"] <= c["limit"] for name, c in checks.items())


def run(root, workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, check_device: bool = True) -> Optional[dict]:
    """The whole run; returns the result line's object, or None where the
    device does not suit the cell (nothing was measured)."""
    layout = Layout(root)
    cell = layout.cell(workload)
    limits = read_limits(layout, workload)
    import jax

    devices = jax.devices()
    if check_device and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        _log(f"chipbench: {workload} needs {cell.chips} TPU chip(s); JAX "
             f"found {len(devices)} {devices[0].platform!r} device(s)")
        return None
    kind = devices[0].device_kind
    peaks = peaks_mod.peaks_for(kind)
    from repro.launch.compile_cache import use_compile_cache
    from repro.obs.trace import Tracer

    cache_dir = use_compile_cache()
    # every program, however quick to compile, is cached: steady set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    # the program's spans only where a metric of the cell reads them
    spans = any(m["source"] == "program_span" for m in cell.per_layer)
    tracer = Tracer() if trace and spans else None
    traffic, system = prepare(cell, seed, tracer)
    if tracer is not None:
        tracer.clear()
    before = counter.programs
    setup_end = time.perf_counter()
    setup_s = setup_end - t_start
    _log(f"[setup] setup_s={setup_s} compile_s={counter.compile_s} "
         f"programs={counter.programs} compile_cache={cache_dir}")
    prof_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        records, calls, prof = drive(system, traffic, seconds, prof_dir)
        _log(f"[window] compiles_in_window={counter.programs - before} "
             f"programs={sorted(set(counter.names[before:]))} "
             f"calls={len(calls)} requests={len(records)} "
             f"drain_s={max(b for _, b, _ in calls) - seconds}")
        _log(f"[window] call_s={[b - a for a, b, _ in calls]}")
        stats_ = [d.memory_stats() or {} for d in devices[:cell.chips]]
        peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats_)
        rec = RunRecord(cell, setup_s, records, calls,
                        tracer.spans() if tracer is not None else [], peaks)
        if prof is not None:
            if prof.start_s is None:
                raise RuntimeError(
                    f"no serve() call started after {PROFILE_AT * seconds} s "
                    f"of the window, so nothing was traced")
            t = time.perf_counter()
            rec.place_trace(xtrace.load(prof_dir, (SERVE_ANNOTATION,
                                                   MARK_ANNOTATION)),
                            prof.start_s, prof.stop_s, prof.mark_s)
            quiet = rec.quiet_calls()

            def per_request(ix):
                ix = list(ix)
                return (sum(calls[i][1] - calls[i][0] for i in ix)
                        / max(1, sum(calls[i][2] for i in ix)))
            # what the profiler costs: call seconds per request with it on
            _log(f"[trace] read_s={time.perf_counter() - t} "
                 f"traced_s={prof.stop_s - prof.start_s} "
                 f"device_recorded_after_s="
                 f"{rec.traced[0] - rec.offset - prof.start_s} "
                 f"whole_calls={len(rec.profiled_calls())} "
                 f"s_per_request_traced="
                 f"{per_request(set(range(len(calls))) - quiet)} "
                 f"s_per_request_quiet={per_request(quiet)}")
    finally:
        if prof_dir is not None:
            shutil.rmtree(prof_dir, ignore_errors=True)
    params = system.params
    del system
    gc.collect()
    t = time.perf_counter()
    failed, checks, _ = check(params, cell, traffic, records, limits, seed)
    _log(f"[check] reference_s={time.perf_counter() - t} failed={failed}")
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = layout.metric(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": failed == 0 and compared_ok(checks),
           "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if rec.trace is not None:
        device["busy_s"] = xtrace.busy_s(rec.trace, [rec.traced])
        device["window_s"] = rec.traced[1] - rec.traced[0]
        out["breakdown"] = {
            "device_ops": xtrace.top_ops(rec.trace),
            "idle_gaps": xtrace.idle_gaps(rec.trace, rec.busy_windows(),
                                          prefer=_program_files())}
    out["checks"] = checks
    return out
