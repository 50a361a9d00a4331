"""Admission and batcher host time per request, in microseconds: host time
inside the window's ``submit_many`` / ``pump`` calls, less the time those
calls spent in the co-scheduler's ``launch_mixed`` and ``gather``, over the
requests submitted."""


def read(ctx):
    win, probes = ctx["window"], ctx["probes"]
    if not ctx["n_submitted"]:
        return None
    own = win.host_call_s - probes.launch_s - probes.gather_s
    return own / ctx["n_submitted"] * 1e6
