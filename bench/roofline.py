"""Operations and bytes of the staging GEMM, from shapes alone, and the
chip's peaks.

The staging GEMM of one channel and one staging pass multiplies the u8
limb planes of N rows (K = w * La columns, w the pass's tile width and La
the data limbs) by the int8 twiddle planes (K x M, M = d * n_diag, n_diag =
La + Lw - 1), giving int32 diagonal sums (N x M).  The count is of the
algorithm's 8-bit limb products on those shapes, whatever dtype a program
lowers the dot to.  Per launch it is summed over channels and passes.
"""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.abspath(__file__))

# Limbs per 32-bit word and RNS channels of each served class.
CLASS_SHAPES = {
    "dilithium": {"data_limbs": 3, "tw_limbs": 3, "channels": 1},
    "bn254": {"data_limbs": 4, "tw_limbs": 4, "channels": 9},
    "bn254_full": {"data_limbs": 4, "tw_limbs": 4, "channels": 18},
}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(ROOT, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def pass_widths(d: int, d_tile: int) -> list:
    return [min(d_tile, d - lo) for lo in range(0, d, d_tile)]


def launch_cost(workload: str, d: int, rows: int, d_tile: int) -> dict:
    """MACs and bytes of the staging GEMMs of one launch of ``rows`` rows."""
    s = CLASS_SHAPES[workload]
    la, n_diag = s["data_limbs"], s["data_limbs"] + s["tw_limbs"] - 1
    m = d * n_diag
    macs = bytes_ = 0
    for w in pass_widths(d, d_tile):
        k = w * la
        macs += rows * k * m
        bytes_ += rows * k + k * m + 4 * rows * m
    return {"macs": macs * s["channels"], "bytes": bytes_ * s["channels"]}


def bound_s(macs: int, bytes_: int, peak: dict) -> tuple:
    """(least seconds, "compute" or "memory"): the larger of the MAC time at
    the int8 peak (2 operations a MAC) and the byte time at HBM bandwidth."""
    t_ops = 2 * macs / peak["int8_ops_per_s"]
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
