"""The program's wall-clock spans, placed on the harness's clock and on the
profiler's.

The program stamps its spans with ``time.perf_counter`` from no particular
origin (``repro.obs.trace.Tracer``).  Only tracks that end in ``/wall`` are
read: the program's other tracks hold spans on its virtual clock.

* Harness clock: the k-th ``serve`` span on ``engine/wall`` is the k-th
  serve() call; the median of (call start - span start) maps the program's
  clock onto the harness's.  The readers keep only the spans inside calls
  that miss the trace, as the host-clock readers do: the profiler's Python
  tracer slows the host while it records.
* Profiler clock: each serve() call inside the trace carries a
  ``chipbench.serve`` annotation, paired with its ``serve`` span.  Where the
  trace holds none (one call outlasts the trace), each
  ``ContinuousBatcher.step`` call that the Python tracer recorded is paired
  with its ``decode_step`` span.  The median of (event start - span start)
  is the offset; a spread over ``MAX_SPREAD_S`` refuses it.
* Idle attribution: each stretch of a device-idle gap in the traced calls
  is charged to the innermost (shortest) wall span covering it, other than
  ``serve``.
"""
from __future__ import annotations

import bisect
import re
import statistics
import sys
from typing import Optional

from . import stats

ENGINE = "engine/wall"
WALL = "/wall"
SERVE_ANNOTATION = "chipbench.serve"
# ContinuousBatcher.step as the profiler's Python tracer names it
STEP_EVENT = re.compile(r"^\$batching\.py:\d+ step$")
MAX_SPREAD_S = 1e-3
NO_SPAN = "(no span)"


def _log(*args) -> None:
    print("[spans]", *args, file=sys.stderr, flush=True)


def wall(run) -> list:
    return [s for s in run.spans if s.track.endswith(WALL)]


def _named(spans, name: str) -> list:
    return sorted((s for s in spans if s.name == name), key=lambda s: s.t0)


def harness_offset(run) -> Optional[float]:
    """Harness clock minus program clock; None unless every serve() call
    has its ``serve`` span."""
    serves = _named((s for s in run.spans if s.track == ENGINE), "serve")
    if not serves or len(serves) != len(run.calls):
        return None
    return statistics.median(c[0] - s.t0 for c, s in zip(run.calls, serves))


def quiet(run, name: str) -> Optional[list]:
    """The wall spans called ``name`` whose middle lies inside a serve()
    call that misses the trace; None where the program's clock cannot be
    tied to the harness's."""
    off = harness_offset(run)
    if off is None:
        return None
    wins = [run.calls[i][:2] for i in run.quiet_calls()]
    return [s for s in wall(run) if s.name == name
            and any(lo <= (s.t0 + s.t1) / 2 + off <= hi for lo, hi in wins)]


def per_request(run, name: str) -> Optional[dict]:
    """Seconds of quiet ``name`` spans on each request's track."""
    spans = quiet(run, name)
    if spans is None:
        return None
    out: dict = {}
    for s in spans:
        out[s.track] = out.get(s.track, 0.0) + s.dur_s
    return out


def pair(spans: list, events: list, coarse: float) -> list:
    """(event start - span start) for each event whose start, less
    ``coarse``, lies nearer one span's start than half the distance from
    that start to its neighbours'."""
    starts = [s.t0 for s in spans]
    out = []
    for e in events:
        t = e[0] - coarse
        i = bisect.bisect_left(starts, t)
        i = min((j for j in (i - 1, i) if 0 <= j < len(starts)),
                key=lambda j: abs(starts[j] - t), default=None)
        if i is None:
            continue
        room = min([abs(starts[j] - starts[i]) for j in (i - 1, i + 1)
                    if 0 <= j < len(starts)] or [float("inf")])
        if abs(starts[i] - t) < room / 2:
            out.append(e[0] - starts[i])
    return out


def profiler_offset(run) -> Optional[float]:
    """Profiler clock minus program clock, from the pairs described in the
    module docstring; None without a trace, without pairs, or where the
    offsets spread over ``MAX_SPREAD_S``."""
    if run.trace is None:
        return None
    h = harness_offset(run)
    if h is None:
        return None
    coarse = run.offset + h       # through the harness's mark
    spans = wall(run)
    offsets = pair(_named((s for s in spans if s.track == ENGINE), "serve"),
                   run.trace.annotations(SERVE_ANNOTATION), coarse)
    source = "serve"
    if not offsets:
        steps = [(float(a), float(b)) for n, a, b in
                 zip(run.trace.host_names, run.trace.host_t0,
                     run.trace.host_t1) if STEP_EVENT.match(n)]
        offsets = pair(_named(spans, "decode_step"), sorted(steps), coarse)
        source = "decode_step"
    if not offsets:
        _log("profiler clock: no span pairs with an event of the trace")
        return None
    spread = max(offsets) - min(offsets)
    _log(f"profiler clock: pairs={len(offsets)} by={source} "
         f"spread_ms={1e3 * spread} offset_minus_coarse_ms="
         f"{1e3 * (statistics.median(offsets) - coarse)}")
    if spread > MAX_SPREAD_S:
        return None
    return statistics.median(offsets)


def charge(gaps: list, spans: list) -> dict:
    """Seconds of ``gaps`` (disjoint intervals) by the name of the shortest
    of ``spans`` ((t0, t1, name)) covering them; ``NO_SPAN`` where none
    does."""
    cuts = sorted({x for g in gaps for x in g}
                  | {x for s in spans for x in s[:2]})
    by_start = sorted(spans)
    gaps = sorted(gaps)
    out: dict = {}
    active: list = []
    j = g = 0
    for lo, hi in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j][0] <= lo:
            active.append(by_start[j])
            j += 1
        active = [s for s in active if s[1] > lo]
        while g < len(gaps) and gaps[g][1] <= lo:
            g += 1
        if g < len(gaps) and gaps[g][0] <= lo:
            name = (min(active, key=lambda s: s[1] - s[0])[2] if active
                    else NO_SPAN)
            out[name] = out.get(name, 0.0) + (hi - lo)
    return out


def idle_by_span(run) -> Optional[dict]:
    """Device-idle seconds of the traced serve() calls by the innermost
    wall span covering them (``charge``), logged; None where the clocks
    cannot be tied or the trace has no device."""
    if run.trace is None or not run.trace.device_ops:
        return None
    off = profiler_offset(run)
    if off is None:
        return None
    ops = [iv for d in run.trace.device_ops
           for iv in run.trace.op_intervals(d)]
    gaps = stats.gaps(ops, run.busy_windows())
    out = charge(gaps, [(s.t0 + off, s.t1 + off, s.name) for s in wall(run)
                        if s.name != "serve"])
    _log(f"idle_s={sum(out.values())} idle_s_by_span="
         f"{dict(sorted(out.items(), key=lambda kv: -kv[1]))}")
    return out


def idle_unattributed(run) -> Optional[float]:
    """Share of the idle seconds that no wall span covers, in %."""
    idle = idle_by_span(run)
    if not idle:
        return None
    return 100.0 * idle.get(NO_SPAN, 0.0) / sum(idle.values())
