"""The four-step BN254 transform (class ``bn254_prover``).

Rows of d field elements, seeded uniform 254-bit values and the all-(p−1)
row, against the plain reference transform of ``bench/reference/bn254.py``
(and its O(d²) definition at d = 64), directly and through a
``CryptoServer``; the on-device field → RNS re-encode against the host; the
envelope that makes the mid-stage reductions necessary; and the packing
metrics a four-step launch reports.  Engines run op by op, unjitted, at the
served settings (int32 accumulation, 171-wide staging tiles); only the
server test compiles the program.
"""
import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field as F
from repro.core import ntt as NTT
from repro.core import rns as R
from repro.core import wordarith as W
from repro.core import workloads as WK
from repro.core.scheduler import TenantRequest
from repro.core.scheduler.coscheduler import SliceCoScheduler
from repro.serve import CryptoServer, ServeConfig
from repro.serve.server import _packing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from bench.reference import bn254 as BN  # noqa: E402

P = F.BN254_FR
SERVED = dict(accum="int32_native", reduction="eager", kappa=None,
              d_tile=171)


def _rows(d: int, seed: int) -> np.ndarray:
    """(3, d) object ints: two seeded uniform rows in [0, p), then p − 1
    everywhere (the largest stage sums)."""
    rng = np.random.default_rng(seed)
    rows = [[int.from_bytes(rng.bytes(32), "little") % P for _ in range(d)]
            for _ in range(2)]
    return np.array(rows + [[P - 1] * d], object)


def _field(row) -> list:
    """(d, nred) digit-12 row -> its d values mod p."""
    return [W.digits_to_int(np.asarray(x)) % P for x in row]


def _evaluate(eng, a: np.ndarray) -> np.ndarray:
    return np.asarray(eng.e2e(eng.ingest(a), planes=eng.device_planes()))


@functools.lru_cache(maxsize=None)
def _served(d: int):
    """The rows of a d and the engine's answer to them (computed once)."""
    a = _rows(d, seed=d)
    return a, _evaluate(WK.make_engine("bn254_prover", d, **SERVED), a)


@pytest.mark.parametrize("d", [64, 1024, 2048])
def test_engine_equals_reference(d):
    """2048 is the non-square split: 64 columns × 32 rows."""
    a, out = _served(d)
    eng = WK.make_engine("bn254_prover", d, **SERVED)
    assert (eng.d1, eng.d2) == {64: (8, 8), 1024: (32, 32),
                                2048: (64, 32)}[d]
    assert out.dtype == np.uint16
    assert out.shape == (3, d, eng.chain.n_red_digits)
    for r in range(len(a)):
        assert _field(out[r]) == BN.evaluate(list(a[r]), d), r


def test_engine_equals_definition_at_64():
    a, out = _served(64)
    for r in range(len(a)):
        assert _field(out[r]) == BN.evaluate_direct(list(a[r]), 64), r


def test_four_step_oracle_is_the_dense_transform():
    """The host decomposition itself, non-square split, against the dense
    matrix of the same root."""
    a = _rows(128, seed=5)
    want = (a @ NTT.ntt_matrix(128, P).astype(object)) % P
    np.testing.assert_array_equal(NTT.four_step_oracle_np(a, P), want)


def test_served_through_crypto_server():
    """The d = 1024 rows through submit_many / pump / drain: the program is
    validated (V1–V5, passes per stage), the rows come back equal, and each
    launch counts 2 transform stages and 3 field reductions."""
    d = 1024
    a, want = _served(d)
    dev = jax.devices()[0]
    cos = SliceCoScheduler({"bn254_prover": [dev]}, accum="int32_native",
                           d_tile=171, row_ladder=(4,))
    server = CryptoServer(ServeConfig(n_c=4, max_age_s=0.005, validate=True,
                                      accum="int32_native", d_tile=171,
                                      row_ladder_max=4, metrics=True),
                          coscheduler=cos)
    eng = cos.engine_for("bn254_prover", d)
    res = np.asarray(eng.ingest(a))
    reqs = [TenantRequest(i, "bn254_prover", d, 0.0, res[i])
            for i in range(len(a))]
    handles = server.submit_many(reqs, now=0.0)
    server.pump(0.001)
    server.drain(0.01)
    assert ("bn254_prover", d) in server._validated
    for i, h in enumerate(handles):
        np.testing.assert_array_equal(h.result(), want[i])
    disp = server.telemetry.snapshot()["dispatch"]
    assert disp["dispatches"] >= 1
    assert disp["transform_stages"] == 2 * disp["dispatches"]
    assert disp["field_reductions"] == 3 * disp["dispatches"]
    text = server.metrics_text()
    assert f"repro_transform_stages_total {disp['transform_stages']}" in text
    assert f"repro_field_reductions_total {disp['field_reductions']}" in text


_BETA = 1 << W.BETA_BITS
FIELD_VALUES = {
    "random": [int(x) for x in np.random.default_rng(7).integers(
        0, 1 << 62, 16)] + [pow(3, 200 + k, P) for k in range(16)],
    "edge": [0, 1, 2, _BETA - 1, _BETA, _BETA ** 21 - 1, _BETA ** 21,
             P - 1, P - 2, P - _BETA, (P - 1) // 2, P >> 12],
}


@pytest.mark.parametrize("kind", sorted(FIELD_VALUES))
def test_field_to_rns_inverts_rns_to_field(kind):
    chain = R.make_chain(18)
    vals = np.array(FIELD_VALUES[kind], object)
    digits = np.stack([W.int_to_digits(int(v), chain.n_red_digits)
                       for v in vals])
    res = np.asarray(R.field_to_rns(jnp.asarray(digits), chain))
    np.testing.assert_array_equal(res, R.to_rns_np(vals, chain))
    back = np.asarray(R.rns_to_field(jnp.asarray(res), chain))
    np.testing.assert_array_equal(back, digits)


def test_field_to_rns_is_exact_at_the_widest_digits():
    """Every digit 4095: each matmul sum at its largest, nd · 4095²."""
    chain = R.make_chain(18)
    digits = np.full((1, chain.n_red_digits), _BETA - 1, np.uint32)
    x = W.digits_to_int(digits[0])
    res = np.asarray(R.field_to_rns(jnp.asarray(digits), chain))
    np.testing.assert_array_equal(res[0], [x % m for m in chain.moduli])


def test_mid_stage_reduction_keeps_the_envelope():
    """Without the reduction after stage 1, the twiddle product of a stage
    sum (up to d1·(p−1)²) exceeds M = 2^527: the all-(p−1) row comes back
    wrong, where the engine as served gets it right."""
    d = 64
    a, _ = _served(d)
    eng = WK.BN254FourStepEngine(d, n_channels=18, **SERVED)
    renormalise = eng._renormalise
    calls = []

    def skip_first(self, y_res):
        calls.append(1)
        return y_res if len(calls) == 1 else renormalise(y_res)

    eng._renormalise = types.MethodType(skip_first, eng)
    out = _evaluate(eng, a)
    assert len(calls) == 2
    assert _field(out[2]) != BN.evaluate(list(a[2]), d)


def test_packing_reads_the_sub_transform():
    """A four-step launch's K is stage 1's d1 columns, so a d = 256 batch
    fills one staging pass of 16 (no re-injection), where the dense
    engine's d = 256 footprint takes 2 passes of 128."""
    batch = types.SimpleNamespace(degrees=[256, 128], d_bucket=256)
    four = _packing(WK.make_engine("bn254_prover", 256), batch, 128)
    assert four.k_occupancy == (16 + 8) / (2 * 16)
    assert four.staging_overhead == 0.0
    dense = _packing(WK.make_engine("bn254_full", 256), batch, 128)
    assert dense.staging_overhead == 0.5


def test_four_step_refuses_lazy_folds():
    """Its validation counts eager passes per stage; lazy windows of two
    stages would share their window scopes."""
    with pytest.raises(ValueError, match="folds eagerly"):
        WK.BN254FourStepEngine(64, reduction="lazy")
