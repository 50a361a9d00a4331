"""The plain references against the program's own host oracles, and the
finding that decides which BN254 class the benchmark serves: the 9-channel
``bn254`` class cannot give the field result of a full-width row, and
``bn254_full`` does."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check as CHK  # noqa: E402
from bench import payloads as PL  # noqa: E402
from bench.reference import bn254 as BN  # noqa: E402
from bench.reference import dilithium as DIL  # noqa: E402


@pytest.mark.parametrize("d", [64, 256])
def test_dilithium_reference_equals_program_oracle(d):
    from repro.core.workloads import DilithiumEngine
    rng = np.random.default_rng(d)
    a = rng.integers(0, DIL.Q, (5, d), dtype=np.int64)
    a[0] = DIL.Q - 1                  # the largest sums the reference meets
    want = DilithiumEngine(d).oracle_np(a.astype(np.uint32))
    np.testing.assert_array_equal(DIL.ntt(a), want.astype(np.int64))


def test_dilithium_reference_is_negacyclic():
    """x^d = -1: multiplying by x rotates and negates the wrapped
    coefficient, which the transform turns into a pointwise product."""
    d, q = 64, DIL.Q
    rng = np.random.default_rng(7)
    a = rng.integers(0, q, d, dtype=np.int64)
    xa = np.roll(a, 1)
    xa[0] = (-a[-1]) % q
    psi = DIL.least_root(q, 2 * d)
    pts = np.array([pow(psi, 2 * j + 1, q) for j in range(d)], np.int64)
    np.testing.assert_array_equal(DIL.ntt(xa[None])[0],
                                  DIL.ntt(a[None])[0] * pts % q)


@pytest.mark.parametrize("d", [64, 128])
def test_bn254_fast_equals_definition_and_program_matrix(d):
    from repro.core.workloads import BN254Engine
    rng = np.random.default_rng(d)
    coeffs = [PL.limbs_to_int(l) for l in PL.field_limbs(d - 3, rng)]
    fast = BN.evaluate(coeffs, d)
    assert fast == BN.evaluate_direct(coeffs, d)
    omega = BN254Engine(d, n_channels=9).omega
    exact = [int(x) % BN.P for x in
             np.array(coeffs + [0, 0, 0], object) @ omega.astype(object)]
    assert fast == exact


def test_field_limbs_are_uniform_below_p():
    rng = np.random.default_rng(1)
    vals = [PL.limbs_to_int(l) for l in PL.field_limbs(2000, rng)]
    assert all(0 <= v < BN.P for v in vals)
    assert max(v.bit_length() for v in vals) == 254
    # the top quarter of [0, p) holds about a quarter of the draws
    share = sum(v >= 3 * BN.P // 4 for v in vals) / len(vals)
    assert 0.2 < share < 0.3


def test_residues_match_python_ints():
    rng = np.random.default_rng(2)
    limbs = PL.field_limbs(50, rng)
    moduli = (2147352577, 2146959361, 65537)
    res = PL.residues(limbs, moduli)
    for row, l in zip(res, limbs):
        v = PL.limbs_to_int(l)
        assert row.tolist() == [v % m for m in moduli]


def digits(x: int, n: int = 22) -> list:
    return [(x >> (CHK.DIGIT_BITS * k)) & 0xFFF for k in range(n)]


def served_rows(degrees, rng):
    """Payload limbs and the right served digits, each value a random
    multiple of p above its reduced value (digits are taken mod p)."""
    items = []
    for deg in degrees:
        limbs = PL.field_limbs(deg, rng)
        want = BN.evaluate([PL.limbs_to_int(l) for l in limbs],
                           CHK.bucket(deg))
        row = np.array([digits(x + int(rng.integers(0, 4)) * BN.P)
                        for x in want], np.uint32)
        items.append((deg, limbs, row))
    return items


@pytest.mark.parametrize("fault,wrong", [
    (None, 0), ("digit", 1), ("coefficient_off_by_p_minus_1", 1),
    ("rows_swapped", 2), ("wide_digit", 1), ("short_row", 1)])
def test_projected_bn254_check(fault, wrong):
    """Every row through the projected check: right rows pass, and each
    planted fault is caught in the row it touches; the exact per-row
    comparison agrees on every row."""
    rng = np.random.default_rng(11)
    items = served_rows([64, 70, 128, 100, 64], rng)
    deg, limbs, row = items[1]
    row = row.copy()
    if fault == "digit":
        row[5, 3] ^= 1
    elif fault == "coefficient_off_by_p_minus_1":
        x = CHK.digits_to_int(row[7]) + BN.P - 1
        row[7] = digits(x)
    elif fault == "rows_swapped":
        items[0], items[4] = ((items[0][0], items[0][1], items[4][2]),
                              (items[4][0], items[4][1], items[0][2]))
    elif fault == "wide_digit":
        row[0, 0] += 1 << 12
    elif fault == "short_row":
        row = row[:64]
    items[1] = (deg, limbs, row)
    compared, got = CHK.check_bn254(items, np.random.default_rng(5))
    assert (compared, got) == (5, wrong)
    if fault != "wide_digit":
        exact = sum(not CHK.check_bn254_row(*it) for it in items)
        assert exact == wrong


@pytest.mark.parametrize("cls,exact", [("bn254", False), ("bn254_full", True)])
def test_only_bn254_full_gives_the_field_result(cls, exact):
    """Full-width rows at d = 64 through the served program: the 9-channel
    class's CRT modulus (248 bits) is below sum a_i Omega_ij, so its rows
    miss the field result; the 18-channel class's (527 bits) is not."""
    import jax
    from repro.core.workloads import make_engine
    d = 64
    eng = make_engine(cls, d, accum="int32_native", d_tile=171)
    rng = np.random.default_rng(3)
    limbs = PL.field_limbs(2 * d, rng).reshape(2, d, 8)
    operand = PL.residues(limbs, eng.chain.moduli)
    rows = np.asarray(jax.jit(eng.e2e)(operand))
    ok = [CHK.check_bn254_row(d, limbs[i], rows[i]) for i in range(2)]
    assert all(ok) if exact else not any(ok)
    _, wrong = CHK.check_bn254([(d, limbs[i], rows[i]) for i in range(2)],
                               np.random.default_rng(4))
    assert wrong == (0 if exact else 2)
    if not exact:
        assert not eng.in_envelope(np.array(
            [PL.limbs_to_int(l) for l in limbs[0]], object))
