"""The co-scheduler's operand staging, in milliseconds per launch: the
program's own ``repro.stage`` phase in the window (member operands to
residues, the merge onto a ladder rung, the host-to-device put) over the
launches the server's telemetry counted.  Read from the phase spans on the
profiler trace."""

from bench import phase_trace as PT


def read(ctx):
    secs = PT.window_phase_s(ctx)
    launches = ctx["telemetry"]["dispatches"]
    if "stage" not in secs or not launches:
        return None
    return secs["stage"] / launches * 1e3
