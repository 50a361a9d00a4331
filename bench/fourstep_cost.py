"""Operations and bytes of one launch of a four-step transform, from shapes.

A length-d transform (d = 2^k) runs as two limb-GEMM stages: d2 = 2^floor(k/2)
column transforms of size d1 = 2^ceil(k/2) per row, then d1 row transforms
of size d2.  Each stage is the staging GEMM of ``bench/roofline.py`` with
the row count multiplied: one launch of ``rows`` rows costs
``launch_cost("bn254_full", d1, rows * d2)`` plus
``launch_cost("bn254_full", d2, rows * d1)`` (the four-step class has
``bn254_full``'s limbs and 18 channels).
"""
from __future__ import annotations

from bench import roofline as RF


def split(d: int) -> tuple:
    """(d1, d2) of a power-of-two d."""
    k = d.bit_length() - 1
    return 1 << (k - k // 2), 1 << (k // 2)


def launch_cost(d: int, rows: int, d_tile: int) -> dict:
    """MACs and bytes of both stages' GEMMs in one launch of ``rows``."""
    d1, d2 = split(d)
    s1 = RF.launch_cost("bn254_full", d1, rows * d2, d_tile)
    s2 = RF.launch_cost("bn254_full", d2, rows * d1, d_tile)
    return {"macs": s1["macs"] + s2["macs"],
            "bytes": s1["bytes"] + s2["bytes"]}
