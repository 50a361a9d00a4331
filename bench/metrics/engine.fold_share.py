"""Share of the device's busy time, in percent, spent in modular reduction:
ops under the named scopes ``vpu_fold`` (eager per-pass folds),
``vpu_fold_lazy`` (deferred folds) and ``vpu_montgomery`` (RNS-to-field
Montgomery reduction) in the profiler trace."""

from bench import trace_reduce as TRR

SCOPES = ("vpu_fold", "vpu_fold_lazy", "vpu_montgomery")


def read(ctx):
    if ctx["trace"] is None or not ctx["busy_s"]:
        return None
    fold = TRR.scope_s(ctx["trace"], SCOPES)
    if not fold:
        return None
    return fold / ctx["busy_s"] * 100.0
