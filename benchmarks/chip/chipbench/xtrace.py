"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Device operations come from each ``/device:TPU:<n>`` plane's ``XLA Ops``
line, named ``<module>/<op>``: the jitted program from the ``XLA Modules``
line that encloses the op (its hash dropped), and the op's HLO name with
its numeric suffix dropped, e.g. ``jit_layer_packed_fn/
flash_attention_quant_op``.  Host events come from the host thread that
carries the harness's annotations; with JAX's Python tracer on they include
one event per Python call (``$<file>.py:<line> <function>``).  All times
are seconds on the profiler's one clock.  Events begun before the
profiler started or ended after it stopped are not in the trace, so the
harness marks the start and maps its own clock onto the profiler's.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

import numpy as np

from . import stats

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Trace:
    # per device: [(name, t0, t1)] of its XLA operations
    device_ops: dict
    # host events of the annotated thread: names and [start, end] arrays
    host_names: list
    host_t0: np.ndarray
    host_t1: np.ndarray

    def annotations(self, name: str) -> list[tuple[float, float]]:
        return [(float(a), float(b)) for n, a, b in
                zip(self.host_names, self.host_t0, self.host_t1) if n == name]

    def op_intervals(self, device: str):
        return [(a, b) for _, a, b in self.device_ops[device]]


def from_events(device_ops: dict, host: list) -> Trace:
    """A trace from plain lists: ``{device: [(name, t0, t1)]}`` and
    ``[(name, t0, t1)]`` of host events."""
    return Trace({d: [tuple(e) for e in ev] for d, ev in device_ops.items()},
                 [h[0] for h in host],
                 np.array([h[1] for h in host], float),
                 np.array([h[2] for h in host], float))


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_name(name: str) -> str:
    """``jit_layer_packed_fn(1516...)`` -> ``jit_layer_packed_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def _device_ops(plane) -> list:
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   module_name(e.name)) for e in lines.get(MODULES_LINE, []))
    starts = [m[0] for m in mods]
    ops = []
    for e in lines.get(OPS_LINE, []):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        mod = mods[i][2] if i >= 0 and mods[i][1] >= e.start_ns else "?"
        ops.append((f"{mod}/{op_name(e.name)}", e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9))
    return ops


def load(log_dir: str, annotations: tuple) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir``; host events are those
    of the threads that carry one of ``annotations``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    device_ops, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device_ops[plane.name] = _device_ops(plane)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                if any(n in annotations for n, _, _ in evs):
                    host += evs
    return from_events(device_ops, host)


def recorded_from(trace: Trace) -> float | None:
    """The time from which every device is recorded: the latest of the
    devices' first operations; None where no device has one."""
    firsts = [min(a for _, a, _ in ops)
              for ops in trace.device_ops.values() if ops]
    return max(firsts) if firsts else None


def busy_s(trace: Trace, windows) -> float:
    """Seconds inside ``windows`` in which an operation ran, averaged over
    the devices."""
    if not trace.device_ops:
        return 0.0
    return sum(stats.total(stats.clip(trace.op_intervals(d), windows))
               for d in trace.device_ops) / len(trace.device_ops)


def idle_share(trace: Trace, windows) -> float | None:
    """1 - busy / window length over ``windows``; None without devices."""
    span = stats.total(windows)
    if not trace.device_ops or span <= 0:
        return None
    return 1.0 - busy_s(trace, windows) / span


def op_time(trace: Trace, match, windows=None) -> tuple[float, int]:
    """(seconds, count) of device operations whose name ``match`` accepts
    and that start inside ``windows`` (anywhere if None), summed over
    devices."""
    def inside(t):
        return windows is None or any(lo <= t <= hi for lo, hi in windows)

    t, n = 0.0, 0
    for ops in trace.device_ops.values():
        for name, a, b in ops:
            if match(name) and inside(a):
                t += b - a
                n += 1
    return t, n


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` operation names with the most device time (seconds,
    averaged over devices)."""
    acc: dict[str, float] = {}
    for ops in trace.device_ops.values():
        for name, a, b in ops:
            acc[name] = acc.get(name, 0.0) + (b - a)
    n = max(1, len(trace.device_ops))
    return [[name, t / n] for name, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def label(trace: Trace, t: float, prefer=frozenset()) -> str:
    """The innermost host event covering time ``t``, preferring Python
    frames in files named in ``prefer``."""
    hit = np.nonzero((trace.host_t0 <= t) & (trace.host_t1 >= t))[0]
    if hit.size == 0:
        return "host (no event)"
    dur = trace.host_t1[hit] - trace.host_t0[hit]
    names = [trace.host_names[i] for i in hit]
    mine = [j for j, n in enumerate(names)
            if n.startswith("$") and n[1:].split(":", 1)[0] in prefer]
    pool = mine or range(len(names))
    return names[min(pool, key=lambda j: dur[j])]


def idle_gaps(trace: Trace, windows, k: int = 10,
              prefer=frozenset()) -> list[list]:
    """The ``k`` longest stretches inside ``windows`` with no operation on
    the first device, each labelled by what the host was doing in its
    middle (see ``label``)."""
    dev = next(iter(trace.device_ops), None)
    if dev is None:
        return []
    gaps = sorted(stats.gaps(trace.op_intervals(dev), windows),
                  key=lambda g: g[0] - g[1])[:k]
    return [[label(trace, (a + b) / 2, prefer), b - a] for a, b in gaps]
