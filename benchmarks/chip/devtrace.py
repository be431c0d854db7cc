"""Reduction of a profiler trace to device busy time, idle gaps and time
per named device operation.

``jax.profiler`` writes an ``.xplane.pb``; each chip is a plane named
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per operation
that ran, named by its HLO instruction (a Pallas kernel's instruction is
named after its jitted wrapper, e.g. ``round_stats_pallas.1``); a scan's
``while`` is an event that spans the operations of its body. Host threads are
planes under ``/host:``; the benchmark's own spans (``bench_*``) sit on
the Python thread. Device and host events share one clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench_window"
SUFFIX = re.compile(r"(\.\d+)+$")
CONTAINERS = {"while", "conditional", "call"}


@dataclass
class Trace:
    """Events as (name, start_ns, end_ns)."""
    devices: dict = field(default_factory=dict)   # plane -> device op events
    host: list = field(default_factory=list)      # every host event
    window: tuple = (0.0, 0.0)                    # the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    return from_profile(ProfileData.from_file(paths[0]))


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event: the trace may print the
    whole instruction (``%round_stats_pallas.3 = (...) custom-call(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def from_profile(profile) -> Trace:
    tr = Trace()
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
    spans = [(s, e) for n, s, e in tr.host if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"trace holds {len(spans)} {WINDOW_SPAN!r} spans")
    tr.window = spans[0]
    return tr


def _clipped(events, lo, hi):
    return sorted((max(s, lo), min(e, hi)) for _, s, e in events
                  if e > lo and s < hi)


def busy_intervals(events, lo, hi):
    """Union of the events' intervals inside [lo, hi], merged."""
    merged = []
    for s, e in _clipped(events, lo, hi):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    lo, hi = trace.window
    per = [sum(e - s for s, e in busy_intervals(ev, lo, hi))
           for ev in trace.devices.values()]
    return 1e-9 * sum(per) / max(len(per), 1)


def idle_gaps(events, lo, hi):
    """(start, end) of every stretch of [lo, hi] with no operation."""
    gaps, at = [], lo
    for s, e in busy_intervals(events, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def op_seconds(trace: Trace, pattern: str) -> list:
    """Per chip, the summed device time of the operations whose name
    matches ``pattern`` (a regular expression, from the start) inside the
    window. Empty where no chip ran such an operation."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    out = []
    for ev in trace.devices.values():
        hits = [(n, s, e) for n, s, e in ev if rx.match(n)]
        if hits:
            out.append(1e-9 * sum(e - s for s, e in _clipped(hits, lo, hi)))
    return out


def _host_label(host, t):
    """The innermost host event running at time ``t``."""
    best = None
    for n, s, e in host:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "no host event"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, by instruction name
    without its numeric suffix (seconds summed over the window, averaged
    over the chips; control-flow containers such as a scan's ``while``,
    which hold other operations, left out), and the longest idle gaps of
    the first chip, each named by what the host was doing in its middle."""
    lo, hi = trace.window
    totals = {}
    for ev in trace.devices.values():
        for n, s, e in ev:
            kind = SUFFIX.sub("", n)
            if e > lo and s < hi and kind not in CONTAINERS:
                totals[kind] = totals.get(kind, 0.0) + (min(e, hi)
                                                        - max(s, lo))
    chips = max(len(trace.devices), 1)
    ops = sorted(((n, 1e-9 * v / chips) for n, v in totals.items()),
                 key=lambda kv: -kv[1])[:top]
    first = next(iter(trace.devices.values()), [])
    gaps = sorted(idle_gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [[_host_label(trace.host, (s + e) / 2),
                           1e-9 * (e - s)] for s, e in gaps]}
