"""The program's own leaf phase spans in a profiler trace, and the
device-idle time each of them covers.

The server and its co-scheduler open a ``jax.profiler.TraceAnnotation``
named ``repro.<phase>`` around a few leaf phases of the serving path
(``repro.obs.tracing.PHASES``); they land on the trace's host plane, on the
clock of the device ops.  ``bench/trace_reduce.py`` keeps the harness's
spans; this module reads the program's, from the same ``.xplane.pb``.  A
trace of a program without phases reads as no phases, so a metric read
from them reports nothing there.
"""
from __future__ import annotations

import os

from bench import trace_reduce as TRR

PREFIX = "repro."
NO_PHASE = "no_phase"      # device-idle time with no phase open
BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(BENCH), "bench_out", "trace")


def load(path: str) -> tuple:
    """``(window, spans)`` of one trace file: the harness's ``window`` span
    as (start_ns, end_ns), or None, and the program's phase spans as sorted
    (start_ns, end_ns, phase) with the prefix taken off."""
    from jax.profiler import ProfileData
    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != TRR.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns),
                                  name[len(PREFIX):]))
                elif name == TRR.WINDOW_SPAN:
                    s = int(ev.start_ns)
                    window = (s, s + int(ev.duration_ns))
    spans.sort()
    return window, spans


_cache: dict = {}


def window_phases(ctx) -> list | None:
    """The phase spans of the traced run a metric reader's ``ctx``
    describes, read from the trace that run left in ``bench_out/trace``;
    None when the run was not traced, and when the trace there is not the
    run's (its ``window`` span differs)."""
    summary = ctx.get("trace")
    if summary is None:
        return None
    try:
        path = TRR.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path), tuple(summary.window))
    if key not in _cache:
        _cache.clear()
        window, spans = load(path)
        _cache[key] = spans if window == tuple(summary.window) else None
    return _cache[key]


def window_phase_s(ctx) -> dict:
    """Seconds in each phase inside the traced run's window; empty when
    the run's trace has no phase spans."""
    spans = window_phases(ctx)
    return phase_s(spans, ctx["trace"].window) if spans else {}


def phase_s(spans: list, window: tuple) -> dict:
    """Seconds in each phase, clipped to ``window``."""
    out: dict = {}
    for s, e, name in spans:
        s, e = TRR.clip(s, e, window)
        if e > s:
            out[name] = out.get(name, 0) + (e - s)
    return {name: ns * 1e-9 for name, ns in out.items()}


def _idle(summary: TRR.Summary, device: str) -> list:
    """One device's idle intervals inside the window, in order."""
    w = summary.window
    busy = sorted(TRR.clip(o.start, o.start + o.dur, w)
                  for o in summary.ops if o.device == device)
    gaps, cursor = [], w[0]
    for s, e in busy:
        if e <= s:
            continue
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if w[1] > cursor:
        gaps.append((cursor, w[1]))
    return gaps


def idle_by_phase(summary: TRR.Summary, spans: list) -> dict:
    """Device-idle seconds in the window by the phase open then, and under
    ``NO_PHASE`` those with none open, averaged over the chips; empty when
    the trace has no device.  Phases are leaf spans of one thread and never
    overlap, so the values add up to the idle time."""
    if not summary.devices:
        return {}
    out: dict = {NO_PHASE: 0}
    for dev in summary.devices:
        j = 0
        for a, b in _idle(summary, dev):
            covered = 0
            while j < len(spans) and spans[j][1] <= a:
                j += 1
            k = j
            while k < len(spans) and spans[k][0] < b:
                s, e, name = spans[k]
                ns = min(e, b) - max(s, a)
                if ns > 0:
                    out[name] = out.get(name, 0) + ns
                    covered += ns
                k += 1
            out[NO_PHASE] += (b - a) - covered
    n = len(summary.devices)
    return {name: ns * 1e-9 / n for name, ns in out.items()}
