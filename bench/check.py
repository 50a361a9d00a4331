"""The comparison that decides ``correct``.

Every answered row is compared with the plain reference of its class:

* Dilithium: against the negacyclic NTT mod q of its payload at the row's
  length (its bucket), coefficient by coefficient;
* BN254: the served digits (little-endian, 12 bits each) of row j are read
  as an integer X_j, and the row's payload a as field elements.  The row is
  right when X = a Omega (mod p), Omega the reference's cyclic evaluation
  matrix.  Each bucket draws one functional r, uniform in F_p^d, from the
  seed, the reference gives w = Omega r (Omega is symmetric, so w is the
  reference transform of r), and every row is checked by

      sum_j X_j r_j = sum_i a_i w_i   (mod p).

  A wrong row passes with probability 1/p < 2^-253 (Freivalds), and the
  check costs O(d) per row instead of the reference transform's
  O(d log d) Python-int operations, so every row of a run is compared.
  Both sides are sums of products of 16-bit limbs, taken exactly by
  float64 matrix products (every partial sum stays below 2^53).

Every number is a count with the limit 0: rows wrong, and admitted requests
never answered.  A row whose length is not its bucket, or with a digit of
12 bits or more, counts as wrong.
"""
from __future__ import annotations

import numpy as np

from bench import payloads as PL
from bench.reference import bn254 as BN
from bench.reference import dilithium as DIL

DIGIT_BITS = 12
LIMB_BITS = 16
FIELD_LIMBS = 16                  # 16-bit limbs of a value below 2^256
BLOCK_ROWS = 512                  # rows per matrix product
_LIMB_WEIGHTS = np.array([1 << (LIMB_BITS * v) for v in range(FIELD_LIMBS)],
                         object)


def bucket(degree: int) -> int:
    """The served row length: the power of two >= max(64, degree)."""
    return max(64, 1 << (int(degree) - 1).bit_length())


def digits_to_int(row) -> int:
    x = 0
    for dgt in reversed(row.tolist()):
        x = (x << DIGIT_BITS) | int(dgt)
    return x


def check_dilithium(items) -> tuple:
    """items: (degree, truth (d,) uint32, served row).  -> (compared, wrong)"""
    wrong = 0
    by_d: dict = {}
    for deg, truth, row in items:
        d = bucket(deg)
        if row is None or row.shape != (d,):
            wrong += 1
            continue
        by_d.setdefault(d, []).append((truth, row))
    for d, group in by_d.items():
        a = np.zeros((len(group), d), np.int64)
        for i, (truth, _) in enumerate(group):
            a[i, :len(truth)] = truth
        want = DIL.ntt(a)
        got = np.stack([row for _, row in group]).astype(np.int64)
        wrong += int(np.sum(np.any(got != want, axis=1)))
    return len(items), wrong


def check_bn254_row(degree: int, limbs, row) -> bool:
    """One row, coefficient by coefficient, against the reference
    transform: the definition the projected check stands for."""
    d = bucket(degree)
    if row is None or row.ndim != 2 or row.shape[0] != d:
        return False
    want = BN.evaluate([PL.limbs_to_int(l) for l in limbs], d)
    got = [digits_to_int(r) % BN.P for r in row]
    return got == want


def _limbs16(values) -> np.ndarray:
    """(len, 16) float64: the 16-bit little-endian limbs of values < 2^256."""
    raw = b"".join(int(v).to_bytes(2 * FIELD_LIMBS, "little")
                   for v in values)
    return np.frombuffer(raw, "<u2").reshape(-1, FIELD_LIMBS).astype(
        np.float64)


class Projection:
    """A bucket's functional r and the reference's w = Omega r, laid out
    for the two matrix products of :func:`check_bn254`.

    ``served``: (d * n_digits, 16), the limbs of 2^(12 k) r_j mod p at row
    j * n_digits + k; ``truth``: (d * 16, 16), the limbs of 2^(16 u) w_i
    mod p at row i * 16 + u.  Against these, a row's digits give the limbs'
    sums of sum_j X_j r_j (each below 2^42), and its payload's 16-bit limbs
    those of sum_i a_i w_i (each below 2^45)."""

    def __init__(self, d: int, n_digits: int, rng: np.random.Generator):
        r = [PL.limbs_to_int(l) for l in PL.field_limbs(d, rng)]
        w = BN.evaluate(r, d)
        p = BN.P
        self.served = _limbs16(rj * (1 << (DIGIT_BITS * k)) % p
                               for rj in r for k in range(n_digits))
        self.truth = _limbs16(wi * (1 << (LIMB_BITS * u)) % p
                              for wi in w for u in range(FIELD_LIMBS))


def _products(arrays: list, matrix: np.ndarray) -> np.ndarray:
    """(len(arrays), 16) int64: each array, flattened and zero-padded to
    the matrix's height, times the matrix, in blocks of rows."""
    out = np.empty((len(arrays), matrix.shape[1]), np.int64)
    buf = np.empty((min(BLOCK_ROWS, len(arrays)), matrix.shape[0]))
    for lo in range(0, len(arrays), BLOCK_ROWS):
        blk = arrays[lo:lo + BLOCK_ROWS]
        for i, x in enumerate(blk):
            buf[i, :x.size] = x.reshape(-1)
            buf[i, x.size:] = 0
        out[lo:lo + len(blk)] = buf[:len(blk)] @ matrix
    return out


def check_bn254(items, rng: np.random.Generator) -> tuple:
    """items: (degree, truth (deg, 8) uint32 limbs, served row).
    -> (compared, wrong)"""
    wrong = 0
    by_shape: dict = {}
    for deg, limbs, row in items:
        d = bucket(deg)
        if (row is None or row.ndim != 2 or row.shape[0] != d
                or int(row.max(initial=0)) >> DIGIT_BITS):
            wrong += 1
            continue
        by_shape.setdefault(row.shape, []).append((limbs, row))
    for (d, n_digits), group in sorted(by_shape.items()):
        proj = Projection(d, n_digits, rng)
        # A closed loop serves each payload many times: its side once.
        slot: dict = {}
        payloads, which = [], []
        for limbs, _ in group:
            k = slot.setdefault(id(limbs), len(payloads))
            if k == len(payloads):
                payloads.append(np.ascontiguousarray(limbs, "<u4")
                                .view("<u2"))
            which.append(k)
        lhs = _products(payloads, proj.truth)[which]
        rhs = _products([row for _, row in group], proj.served)
        gap = ((lhs - rhs).astype(object) * _LIMB_WEIGHTS).sum(axis=1)
        wrong += int(np.count_nonzero(gap % BN.P != 0))
    return len(items), wrong


def run_checks(records, rng: np.random.Generator) -> dict:
    """records: (workload, degree, truth, served row or None, answered).

    Returns ``{name: {"value": n, "limit": 0}}`` plus the counts compared."""
    dil = [(deg, t, row) for w, deg, t, row, ok in records
           if ok and w == "dilithium"]
    bn = [(deg, t, row) for w, deg, t, row, ok in records
          if ok and w != "dilithium"]
    unanswered = sum(1 for *_, ok in records if not ok)
    n_dil, dil_wrong = check_dilithium(dil) if dil else (0, 0)
    n_bn, bn_wrong = check_bn254(bn, rng) if bn else (0, 0)
    checks = {"unanswered": {"value": unanswered, "limit": 0}}
    if dil:
        checks["dilithium_rows_wrong"] = {"value": dil_wrong, "limit": 0}
    if bn:
        checks["bn254_rows_wrong"] = {"value": bn_wrong, "limit": 0}
    return {"checks": checks,
            "compared": {"dilithium_rows": n_dil, "bn254_rows": n_bn}}
