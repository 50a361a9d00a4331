"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read:

* device operations: the events on the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane, named by their HLO instruction.  The op's
  source path of ``jax.named_scope``s is the ``tf_op`` stat of the
  metadata that the event's own ``metadata_id`` names (read by
  ``bench/xplane.py``; names repeat across programs), which is how an op
  is attributed to a scope such as ``vpu_montgomery``;
* host spans: the ``jax.profiler.TraceAnnotation`` events the harness
  writes on the host plane (``window``, ``submit``, ``pump``, ``launch``,
  ``gather``, ``wait_arrival``).  Both planes share the trace's clock.

What is computed (all in seconds, clipped to the ``window`` span):

* ``busy_s``: the union of device-op intervals, averaged over the chips;
* device time by named scope;
* the device ops that took the most time, and the longest idle gaps of
  the device, each labelled with the host span open at its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("submit", "pump", "launch", "gather", "wait_arrival")
WINDOW_SPAN = "window"
SCOPE_STAT = "tf_op"


@dataclasses.dataclass
class Op:
    start: int        # ns on the trace clock
    dur: int          # ns
    name: str
    scope: str        # the op's named-scope path, "" when none is recorded
    device: str


@dataclasses.dataclass
class Summary:
    window: tuple      # (start_ns, end_ns)
    ops: list          # Op, on every device plane
    spans: list        # (start_ns, end_ns, name) host spans
    devices: list      # device plane names

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _stats(ev) -> dict:
    try:
        return {str(k): v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Summary:
    """Read one trace file into a :class:`Summary`."""
    from jax.profiler import ProfileData
    from bench import xplane
    pd = ProfileData.from_file(path)
    metas = xplane.line_events(path, DEVICE_PLANE_PREFIX, OPS_LINE)
    ops, spans, devices, window = [], [], [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = [l for l in plane.lines if l.name == OPS_LINE]
            if not lines:
                continue
            devices.append(plane.name)
            evs = [ev for line in lines for ev in line.events]
            meta = metas.get(plane.name, [])
            if len(meta) != len(evs):
                raise ValueError(f"{path}: {plane.name} has {len(evs)} ops "
                                 f"but {len(meta)} decoded events")
            for ev, m in zip(evs, meta):
                if ev.name not in (m.name, m.display_name):
                    raise ValueError(f"{path}: op {ev.name!r} decoded as "
                                     f"{m.name!r}: events out of step")
                ops.append(Op(int(ev.start_ns), int(ev.duration_ns),
                              ev.name, m.stats.get(SCOPE_STAT, ""),
                              plane.name))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns) + int(ev.duration_ns))
                    elif ev.name in HOST_SPANS:
                        spans.append((int(ev.start_ns),
                                      int(ev.start_ns) + int(ev.duration_ns),
                                      ev.name))
    if window is None:
        raise ValueError(f"{path}: no '{WINDOW_SPAN}' span on {HOST_PLANE}")
    spans.sort()
    return Summary(window=window, ops=ops, spans=spans, devices=devices)


def clip(start: int, end: int, window: tuple) -> tuple:
    return max(start, window[0]), min(end, window[1])


def union_ns(intervals, window: tuple) -> int:
    """Length of the union of (start, end) intervals inside ``window``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(clip(s, e, window) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(summary: Summary) -> float:
    """Device busy seconds in the window, averaged over the chips."""
    if not summary.devices:
        return 0.0
    total = 0
    for dev in summary.devices:
        total += union_ns(((o.start, o.start + o.dur) for o in summary.ops
                           if o.device == dev), summary.window)
    return total * 1e-9 / len(summary.devices)


def _in_window(summary: Summary):
    w = summary.window
    for o in summary.ops:
        s, e = clip(o.start, o.start + o.dur, w)
        if e > s:
            yield o, e - s


def scope_s(summary: Summary, scopes) -> float:
    """Device seconds of ops whose scope path holds any of ``scopes`` as a
    whole path component, averaged over the chips."""
    want = set(scopes)
    total = sum(ns for o, ns in _in_window(summary)
                if want & set(o.scope.split("/")))
    return total * 1e-9 / max(1, len(summary.devices))


def top_ops(summary: Summary, k: int = 10) -> list:
    """[[op name, seconds], ...]: the k ops with the most device time."""
    by: dict = {}
    for o, ns in _in_window(summary):
        by[o.name] = by.get(o.name, 0) + ns
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def host_span_at(spans: list, t: int) -> str:
    """The innermost host span open at ``t`` (the latest-starting one that
    covers it), or ``"host_other"``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "host_other"


def idle_gaps(summary: Summary, k: int = 10) -> list:
    """[[host span, seconds], ...]: the k longest device-idle gaps in the
    window, each named by the host span open at its midpoint."""
    w = summary.window
    ivs = sorted(clip(o.start, o.start + o.dur, w) for o, _ in
                 _in_window(summary))
    gaps, cursor = [], w[0]
    for s, e in ivs:
        if s > cursor:
            gaps.append((s - cursor, cursor, s))
        cursor = max(cursor, e)
    if w[1] > cursor:
        gaps.append((w[1] - cursor, cursor, w[1]))
    gaps.sort(reverse=True)
    return [[host_span_at(summary.spans, (a + b) // 2), g * 1e-9]
            for g, a, b in gaps[:k]]


def describe(path: str, per_line: int = 5) -> str:
    """A plain dump of planes, lines, and a few events with their stats,
    for looking at a trace by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                out.append(f"    {ev.name!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} stats={_stats(ev)}")
    return "\n".join(out)
