"""Admission host time per request, in microseconds: the program's own
``repro.admit`` phase in the window (the duplicate screen, the vectorised
admission decisions and the handles of ``submit_many``) over the requests
submitted.  Read from the phase spans on the profiler trace."""

from bench import phase_trace as PT


def read(ctx):
    secs = PT.window_phase_s(ctx)
    if "admit" not in secs or not ctx["n_submitted"]:
        return None
    return secs["admit"] / ctx["n_submitted"] * 1e6
