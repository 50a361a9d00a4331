"""Share of the device's busy time, in percent, that the four-step
transform spends outside its GEMMs: ops under the named scopes
``vpu_fold`` (the per-pass folds of both stages), ``vpu_montgomery`` (its
three RNS-to-field reductions), ``rns_encode`` (its two field-to-RNS
re-encodes) and ``fourstep_twiddle`` (the twiddle product, where XLA
leaves it a fusion of its own) in the profiler trace, in a window that
launched the four-step class."""

from bench import trace_reduce as TRR

SCOPES = ("vpu_fold", "vpu_montgomery", "rns_encode", "fourstep_twiddle")
WORKLOAD = "bn254_prover"


def read(ctx):
    if ctx["trace"] is None or not ctx["busy_s"]:
        return None
    if not any(ln[0] == WORKLOAD for ln in ctx["probes"].launches):
        return None
    share = TRR.scope_s(ctx["trace"], SCOPES)
    return share / ctx["busy_s"] * 100.0 if share else None
