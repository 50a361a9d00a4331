"""Pallas kernels (interpret=True on CPU) vs pure-jnp oracles, shape sweeps.

Every kernel call passes ``interpret=True`` itself: the kernel entry points
compile with Mosaic by default.  The Mosaic compiles for a described v5e
live in tests/test_tpu_compile.py."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import field as F
from repro.core import limb_gemm as G
from repro.core import ntt as NTT
from repro.core import workloads as WK
from repro.kernels import (limb_matmul, mont_fold, fused_ntt_tile,
                           pallas_tile_fn, pallas_fused_transform,
                           diag_major, fused_operand_diag_major)
from repro.kernels.limb_matmul.ref import limb_matmul_ref
from repro.kernels.mont_fold.ref import mont_fold_ref
from repro.kernels.fused_ntt_tile.ref import fused_ntt_tile_ref

RNG = np.random.default_rng(42)


def _rand_u8(shape):
    return jnp.asarray(RNG.integers(0, 256, shape, dtype=np.uint8))


def _rand_s8(shape):
    return jnp.asarray(RNG.integers(-128, 128, shape), jnp.int8)


@pytest.mark.parametrize("n,k,m", [
    (8, 512, 1792),    # BN254 staging pass (dt=128, La=4, d=256, 7 diagonals)
    (16, 513, 1280),   # Dilithium pass 1 (dt=171, La=3, d=256, 5 diagonals)
    (3, 100, 70),      # ragged small
    (128, 256, 128),   # MXU-square
])
def test_limb_matmul_int32_sweep(n, k, m):
    a, b = _rand_u8((n, k)), _rand_s8((k, m))
    got = limb_matmul(a, b, accum="int32_native", interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(limb_matmul_ref(a, b)))


def test_limb_matmul_fp32_model():
    # K bounded so partial sums stay inside the 2^24 window -> exact
    a, b = _rand_u8((8, 256)), _rand_s8((256, 384))
    got = limb_matmul(a, b, accum="fp32_mantissa", interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(limb_matmul_ref(a, b, "fp32_mantissa")))


@pytest.mark.parametrize("n,d,n_diag,m", [
    (8, 256, 7, 2013265921),
    (5, 300, 5, F.DILITHIUM_Q),
    (16, 64, 7, (1 << 31) - 99),
])
def test_mont_fold_sweep(n, d, n_diag, m):
    diags = jnp.asarray(RNG.integers(-(2**24), 2**24, (n, d, n_diag)), jnp.int32)
    got = mont_fold(diags, m, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(mont_fold_ref(diags, m)))


@pytest.mark.parametrize("accum", ["int32_native", "fp32_mantissa"])
def test_fused_tile_vs_ref(accum):
    n, k, d, n_diag = 8, 384, 256, 5
    a = _rand_u8((n, k))
    b3 = _rand_s8((k, d, n_diag))
    m = F.DILITHIUM_Q
    got = fused_ntt_tile(a, diag_major(b3), modulus=m, n_diag=n_diag,
                         accum=accum, interpret=True)
    want = fused_ntt_tile_ref(a, b3, m, accum)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_staged_transform_with_pallas_kernel():
    """Engine path with the Pallas matmul == jnp path == bignum oracle."""
    m, d = F.DILITHIUM_Q, 256
    w = NTT.ntt_matrix(d, m, negacyclic=True)
    plan = G.make_channel_plan(w, m, data_limbs=3, tw_limbs=3)
    a = np.asarray(RNG.integers(0, m, (8, d), dtype=np.uint64), np.uint32)
    y_kernel, _ = G.staged_transform(jnp.asarray(a), plan,
                                     kernel_fn=pallas_tile_fn(interpret=True))
    np.testing.assert_array_equal(np.asarray(y_kernel),
                                  NTT.matrix_ntt_oracle_np(a, w, m))


def test_lazy_kappa_window_with_pallas_kernels():
    """Full-kernel lazy path: Pallas limb matmul per pass + Pallas mont_fold
    once per κ-window == eager jnp path (deferred reduction through the
    kernel ops, paper §7.2.1)."""
    from repro.kernels import mont_fold_window_fn
    m, d = F.DILITHIUM_Q, 256
    w = NTT.ntt_matrix(d, m, negacyclic=True)
    plan = G.make_channel_plan(w, m, data_limbs=3, tw_limbs=3,
                               accum="int32_native")
    a = np.asarray(RNG.integers(0, m, (8, d), dtype=np.uint64), np.uint32)
    eager, _ = G.staged_transform(jnp.asarray(a), plan, d_max=171)
    lazy, stats = G.staged_transform(
        jnp.asarray(a), plan, reduction="lazy", kappa=2, d_max=171,
        kernel_fn=pallas_tile_fn(interpret=True),
        fold_fn=mont_fold_window_fn(interpret=True))
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(lazy))
    assert stats["n_folds"] == 1 and stats["n_passes"] == 2


def test_pallas_fused_transform_matches():
    m, d = F.DILITHIUM_Q, 256
    w = NTT.ntt_matrix(d, m, negacyclic=True)
    plan = G.make_channel_plan(w, m, data_limbs=3, tw_limbs=3)
    a = np.asarray(RNG.integers(0, m, (4, d), dtype=np.uint64), np.uint32)
    y = pallas_fused_transform(jnp.asarray(a), plan, interpret=True)
    np.testing.assert_array_equal(np.asarray(y),
                                  NTT.matrix_ntt_oracle_np(a, w, m))


def test_bn254_engine_with_pallas():
    d = 32
    rng = np.random.default_rng(5)
    omega = np.array([[int.from_bytes(rng.bytes(11), "little") for _ in range(d)]
                      for _ in range(d)], object)
    eng = WK.BN254Engine(d, evaluation_matrix=omega)
    coeffs = np.array([[int.from_bytes(rng.bytes(16), "little") for _ in range(d)]
                       for _ in range(2)], object)
    a_res = eng.ingest(coeffs)
    y_plain = np.asarray(eng.evaluate(a_res))
    y_kernel = np.asarray(
        eng.evaluate(a_res, kernel_fn=pallas_tile_fn(interpret=True)))
    np.testing.assert_array_equal(y_plain, y_kernel)


def test_fused_operand_3d_layout():
    m, d = F.DILITHIUM_Q, 64
    w = NTT.ntt_matrix(d, m, negacyclic=True)
    plan = G.make_channel_plan(w, m, data_limbs=3, tw_limbs=3)
    b = fused_operand_diag_major(plan)
    assert b.shape == (d * 3, d * 5)
    # d < 128 is one coefficient block: column k·d + j holds diagonal k of
    # coefficient j, which the plan keeps at column j·n_diag + k.
    b3 = plan.fused_operand.reshape(d * 3, d, 5)
    for k in range(5):
        np.testing.assert_array_equal(b[:, k * d:(k + 1) * d], b3[..., k])
    # d = 256 splits into two 128-wide blocks, each diagonal-major.
    x = np.arange(2 * 256 * 5).reshape(2, 256, 5)
    y = diag_major(x)
    for j in range(2):
        for k in range(5):
            np.testing.assert_array_equal(
                y[:, j * 640 + k * 128:j * 640 + (k + 1) * 128],
                x[:, j * 128:(j + 1) * 128, k])
