"""Chips busy at once, on average over the time in which any is busy: the
sum over chips of each chip's busy seconds in the window over the seconds
in which at least one chip is busy (unions of device-op intervals in the
profiler trace).  1.0 when the chips take turns, the number of chips when
all of them are always busy together."""

from bench import trace_reduce as TRR


def read(ctx):
    summary = ctx["trace"]
    if summary is None or not summary.devices:
        return None
    any_busy = TRR.union_ns(((o.start, o.start + o.dur)
                             for o in summary.ops), summary.window) * 1e-9
    if any_busy <= 0:
        return None
    return sum(TRR.busy_by_device(summary).values()) / any_busy
