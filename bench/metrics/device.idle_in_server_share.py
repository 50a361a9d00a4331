"""Share of the device's idle time in the window, in percent, during which
one of the program's own phases was open on the host: device-idle time
covered by a ``repro.<phase>`` span over all device-idle time (profiler
trace; host and device events share its clock).  The rest is idle time in
the harness, in the program outside its phases, or in no call at all."""

from bench import phase_trace as PT


def read(ctx):
    summary = ctx["trace"]
    if summary is None or not summary.devices:
        return None
    spans = PT.window_phases(ctx)
    if not spans:
        return None
    by = PT.idle_by_phase(summary, spans)
    idle = sum(by.values())
    if idle <= 0:
        return None
    return (idle - by[PT.NO_PHASE]) / idle * 100.0
