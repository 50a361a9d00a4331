"""Request payloads, drawn from the seed in bulk.

Dilithium coefficients are uniform in [0, q).  BN254 coefficients are
uniform in [0, p), 254 bits wide: drawn as eight 32-bit limbs (the top one
below 2^30) and kept where the value is below p.  The server takes a BN254
row as its residues modulo the class's RNS channel moduli, which are made
here from the limbs by a vectorised Horner pass; the limbs themselves are
kept as the truth the reference is given.
"""
from __future__ import annotations

import numpy as np

from bench.reference.bn254 import P
from bench.reference.dilithium import Q

P_LIMBS = np.array([(P >> (32 * k)) & 0xFFFFFFFF for k in range(8)],
                   np.uint64)


def limbs_to_int(limbs) -> int:
    return sum(int(x) << (32 * k) for k, x in enumerate(limbs))


def _below_p(limbs: np.ndarray) -> np.ndarray:
    """Row-wise limbs < P, comparing from the top limb down."""
    less = np.zeros(len(limbs), bool)
    equal = np.ones(len(limbs), bool)
    for k in range(7, -1, -1):
        less |= equal & (limbs[:, k] < P_LIMBS[k])
        equal &= limbs[:, k] == P_LIMBS[k]
    return less


def field_limbs(n: int, rng: np.random.Generator) -> np.ndarray:
    """(n, 8) uint32 little-endian limbs of n values uniform in [0, p)."""
    out, have = [], 0
    while have < n:
        m = int((n - have) * 1.4) + 64
        draw = rng.integers(0, 1 << 32, (m, 8), dtype=np.uint64)
        draw[:, 7] >>= np.uint64(2)                 # 254-bit candidates
        keep = draw[_below_p(draw)]
        out.append(keep[: n - have])
        have += len(out[-1])
    return np.concatenate(out).astype(np.uint32)


def residues(limbs: np.ndarray, moduli) -> np.ndarray:
    """(..., 8) limbs -> (..., len(moduli)) uint32 residues; each modulus
    below 2^31, so r * 2^32 + limb stays below 2^64."""
    lim = limbs.astype(np.uint64)
    out = np.empty(limbs.shape[:-1] + (len(moduli),), np.uint32)
    for c, m in enumerate(moduli):
        m64 = np.uint64(m)
        r = np.zeros(limbs.shape[:-1], np.uint64)
        for k in range(7, -1, -1):
            r = ((r << np.uint64(32)) + lim[..., k]) % m64
        out[..., c] = r.astype(np.uint32)
    return out


def make_payloads(workloads, degrees, rng: np.random.Generator,
                  moduli_for, pool: int = 1 << 20) -> tuple[list, list]:
    """Per request: the served payload and the truth for the reference.

    Each class draws one pool of at most ``pool`` coefficients, and each
    request takes a window of it at an offset drawn from ``rng``, so that
    a long window's traffic costs little set-up.  ``moduli_for(workload)``
    gives a BN254 class's channel moduli.  Returns ``(served, truth)``:
    Dilithium rows are (d,) uint32 for both; BN254 rows are (d, C) uint32
    residues served and (d, 8) uint32 limbs kept."""
    served: list = [None] * len(workloads)
    truth: list = [None] * len(workloads)
    degrees = np.asarray(degrees, np.int64)
    for w in sorted(set(workloads)):
        idx = [i for i, x in enumerate(workloads) if x == w]
        n = int(min(degrees[idx].sum(), pool))
        if w == "dilithium":
            coeffs = rng.integers(0, Q, n, dtype=np.int64).astype(np.uint32)
            kept = coeffs
        else:
            kept = field_limbs(n, rng)
            coeffs = residues(kept, moduli_for(w))
        offsets = rng.integers(0, n - degrees[idx] + 1)
        for i, off in zip(idx, offsets.tolist()):
            sl = slice(off, off + int(degrees[i]))
            served[i], truth[i] = coeffs[sl], kept[sl]
    return served, truth
