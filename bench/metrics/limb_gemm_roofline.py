"""The staging GEMM's share of its roofline, in percent: the least time the
chip could take for the window's staging GEMMs (their 8-bit limb products
at the int8 peak, or their bytes at HBM bandwidth, whichever is longer;
counted from shapes by ``bench/roofline.py``) over the device time of ops
under the ``mxu_pointwise`` scope in the profiler trace."""

from bench import roofline as RF
from bench import trace_reduce as TRR


def read(ctx):
    if ctx["trace"] is None:
        return None
    gemm_s = TRR.scope_s(ctx["trace"], ("mxu_pointwise",))
    launches = ctx["probes"].launches
    if not gemm_s or not launches:
        return None
    d_tile = ctx["cfg"]["serving"]["d_tile"]
    macs = bytes_ = 0
    for workload, d, rows, *_ in launches:
        c = RF.launch_cost(workload, d, rows, d_tile)
        macs += c["macs"]
        bytes_ += c["bytes"]
    least, _ = RF.bound_s(macs, bytes_, RF.peaks(ctx["device_kind"]))
    return least / gemm_s * 100.0
