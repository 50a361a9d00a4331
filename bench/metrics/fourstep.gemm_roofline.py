"""The four-step transform's limb GEMMs' share of their roofline, in
percent: the least time the chip could take for the window's sub-transform
GEMMs (both stages of every ``bn254_prover`` launch, counted from shapes by
``bench/fourstep_cost.py``, at the int8 peak or HBM bandwidth, whichever is
longer) over the device time of ops under the ``mxu_pointwise`` scope."""

from bench import fourstep_cost as FC
from bench import roofline as RF
from bench import trace_reduce as TRR

WORKLOAD = "bn254_prover"


def read(ctx):
    if ctx["trace"] is None:
        return None
    gemm_s = TRR.scope_s(ctx["trace"], ("mxu_pointwise",))
    launches = [ln for ln in ctx["probes"].launches if ln[0] == WORKLOAD]
    if not gemm_s or not launches:
        return None
    d_tile = ctx["cfg"]["serving"]["d_tile"]
    macs = bytes_ = 0
    for _, d, rows, *_ in launches:
        c = FC.launch_cost(d, rows, d_tile)
        macs += c["macs"]
        bytes_ += c["bytes"]
    least, _ = RF.bound_s(macs, bytes_, RF.peaks(ctx["device_kind"]))
    return least / gemm_s * 100.0
