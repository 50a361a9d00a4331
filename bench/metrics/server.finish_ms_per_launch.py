"""The server's host time after each gather, in milliseconds per launch:
the program's own ``repro.account`` (service estimate, packing metrics,
dispatch and batch records, penalty ledger) and ``repro.resolve`` (handles,
latency and wait counters) phases in the window over the launches the
server's telemetry counted.  Read from the phase spans on the profiler
trace."""

from bench import phase_trace as PT


def read(ctx):
    secs = PT.window_phase_s(ctx)
    launches = ctx["telemetry"]["dispatches"]
    if "resolve" not in secs or not launches:
        return None
    return (secs.get("account", 0.0) + secs["resolve"]) / launches * 1e3
