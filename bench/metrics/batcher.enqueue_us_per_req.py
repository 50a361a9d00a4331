"""Batcher host time per request, in microseconds: the program's own
``repro.enqueue`` phase in the window (each admitted request stacked onto
its open batch, the closes and operand stacking those adds and the age
polls make) over the requests submitted.  Read from the phase spans on the
profiler trace."""

from bench import phase_trace as PT


def read(ctx):
    secs = PT.window_phase_s(ctx)
    if "enqueue" not in secs or not ctx["n_submitted"]:
        return None
    return secs["enqueue"] / ctx["n_submitted"] * 1e6
