"""Staging of launch operands: one host write per byte, one placement.

A launch's member operands are merged on the host into a buffer whose pad
rows alone are zeroed, and the merged operand goes onto its device group in
a single ``jax.device_put`` with the co-scheduler's cached sharding (rows
split over the group when they divide evenly, whole otherwise).  Warm-up
places through the same funnel, so a warmed program is hit, not retraced.
Each case checks, inside the ``stage`` phase: one ``device_put`` a launch and
no ``jnp.asarray``; the committed sharding; zero pad rows; the per-launch
``placements`` and ``staged_bytes`` records; rows bit-equal to the engines'
plain references.  The two-device case runs in a child process with two
forced host devices (this process's device count is fixed at JAX start).
"""
import os
import subprocess
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field as F
from repro.core import wordarith as W
from repro.core import workloads as WK
from repro.core.scheduler import TenantRequest
from repro.core.scheduler.coscheduler import SliceCoScheduler
from repro.core.scheduler.rectangular import (StackedBatch, merge_operands,
                                              stack_rows)
from repro.obs import Phases
from repro.serve import CryptoServer, ServeConfig

LADDER = (8, 16)
CLASSES = ("dilithium", "bn254_full")

# name -> (workload, d, rows of each stacked batch, devices in the group)
CASES = {
    "dilithium-single-at-rung": ("dilithium", 64, (8,), 1),
    "dilithium-merged-pad": ("dilithium", 64, (8, 8, 2, 1), 1),
    "bn254_full-single-at-rung": ("bn254_full", 16, (8,), 1),
    "bn254_full-merged-pad": ("bn254_full", 16, (2, 3), 1),
    "dilithium-two-devices-rows": ("dilithium", 64, (3, 2, 1), 2),
}

_COS: dict = {}


def _coscheduler(n_dev: int) -> SliceCoScheduler:
    """One co-scheduler per group size, shared by the cases of a process
    (each warms what it launches; a warm program is not traced again)."""
    if n_dev not in _COS:
        devs = jax.devices()[:n_dev]
        _COS[n_dev] = SliceCoScheduler({w: devs for w in CLASSES},
                                       row_ladder=LADDER)
    return _COS[n_dev]


def _requests(workload: str, d: int, n: int, rng, tid0: int):
    """``n`` requests of full degree, and the plain reference of each row:
    the NTT mod q (Dilithium) or the field evaluation mod p (BN254)."""
    eng = WK.make_engine(workload, d)
    if workload == "dilithium":
        coeffs = np.asarray(rng.integers(0, F.DILITHIUM_Q, (n, d),
                                         dtype=np.uint64), np.uint32)
        payloads, want = coeffs, eng.oracle_np(coeffs)
    else:
        coeffs = np.array([[int.from_bytes(rng.bytes(32), "little")
                            % F.BN254_FR for _ in range(d)]
                           for _ in range(n)], object)
        payloads = np.asarray(eng.ingest(coeffs))
        want = eng.oracle_eval_np(coeffs) % F.BN254_FR
    reqs = [TenantRequest(tid0 + i, workload, d, 0.0, payloads[i])
            for i in range(n)]
    return reqs, want


def _row_equal(workload: str, got: np.ndarray, want) -> bool:
    if workload == "dilithium":
        return np.array_equal(got, want)
    return all(W.digits_to_int(got[j]) == want[j] for j in range(len(want)))


class _StageSpy:
    """Counts calls of ``jax.device_put`` and ``jnp.asarray`` made while the
    co-scheduler's ``stage`` phase is open, keeping each placement's host
    operand and the array it returned."""

    def __init__(self, phases: Phases):
        self.inner, self.open = phases.stage, False
        phases.stage = self
        self.asarray_calls = 0
        self.placed: list = []     # (host operand, placed array)
        self._put, self._asarray = jax.device_put, jnp.asarray

    def __enter__(self):
        self.open = True
        return self.inner.__enter__()

    def __exit__(self, *exc):
        self.open = False
        return self.inner.__exit__(*exc)

    def device_put(self, x, *args, **kw):
        out = self._put(x, *args, **kw)
        if self.open:
            self.placed.append((x, out))
        return out

    def asarray(self, *args, **kw):
        if self.open:
            self.asarray_calls += 1
        return self._asarray(*args, **kw)


def check_stage(case: str):
    workload, d, heights, n_dev = CASES[case]
    assert jax.device_count() >= n_dev
    cos = _coscheduler(n_dev)
    cos.precompile([(workload, d)], n_c=LADDER[0])
    traces = dict(cos.trace_counts)

    rng = np.random.default_rng(sum(map(ord, case)))
    batches, wants = [], []
    for i, n in enumerate(heights):
        reqs, want = _requests(workload, d, n, rng, tid0=100 * i)
        batches.append(StackedBatch(workload=workload, d_bucket=d,
                                    requests=reqs,
                                    operand=stack_rows(reqs, d)))
        wants.append(want)

    cos.phases = Phases()
    spy = _StageSpy(cos.phases)
    cos.drain_dispatch_log()
    with mock.patch.object(jax, "device_put", spy.device_put), \
            mock.patch.object(jnp, "asarray", spy.asarray):
        results = cos.dispatch_mixed(batches)
    log = cos.drain_dispatch_log()

    # one placement a launch, no jnp round trip, nothing retraced
    assert spy.asarray_calls == 0
    assert len(spy.placed) == len(log) >= 1
    assert [e["placements"] for e in log] == [1] * len(log)
    assert cos.trace_counts == traces
    assert sum(e["staged_bytes"] for e in log) == sum(
        host.nbytes for host, _ in spy.placed)

    # committed with the cached sharding; pad rows zero, members verbatim
    members = iter(b.operand for b in batches)
    pad_rows = 0
    for (host, placed), entry in zip(spy.placed, log):
        assert isinstance(host, np.ndarray)
        rows = host.shape[0]
        assert rows == entry["launched_rows"] == cos.launch_rows(rows)
        sharding = cos._sharding(workload, rows)
        assert placed.committed and placed.sharding is sharding
        split = n_dev > 1 and rows % n_dev == 0
        assert sharding.spec == (jax.sharding.PartitionSpec("rows") if split
                                 else jax.sharding.PartitionSpec())
        assert set(entry["devices"]) == {dv.id for dv in
                                         jax.devices()[:n_dev]}
        lo = 0
        for _ in range(entry["n_batches"]):
            op = next(members)
            np.testing.assert_array_equal(host[lo:lo + len(op)], op)
            lo += len(op)
        assert not host[lo:].any()
        pad_rows += rows - lo
    assert (pad_rows > 0) == (sum(heights) not in LADDER)
    if len(heights) == 1 and heights[0] in LADDER:   # placed without a copy
        assert spy.placed[0][0] is batches[0].operand

    # rows equal the plain references
    for res, want in zip(results, wants):
        for j in range(len(want)):
            assert _row_equal(workload, res.rows[j], want[j])


@pytest.mark.parametrize("case", sorted(CASES))
def test_stage_places_each_operand_once(case):
    if CASES[case][3] <= jax.device_count():
        check_stage(case)
        return
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.abspath(os.path.join(os.path.dirname(WK.__file__),
                                       os.pardir, os.pardir))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count="
                          f"{CASES[case][3]}").strip(),
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, here, os.environ.get("PYTHONPATH"))
                   if p))
    out = subprocess.run(
        [sys.executable, "-c",
         f"import test_stage_placement as T; T.check_stage({case!r})"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]


@pytest.mark.parametrize("heights", [(3,), (3, 2), (8, 5)])
def test_merge_zeroes_only_pad_rows(heights):
    """``np.empty`` leaves garbage; the merge must overwrite every byte:
    members verbatim, then zeros up to the rung."""
    rng = np.random.default_rng(len(heights))
    ops = [rng.integers(1, 2**32, (n, 4, 3), dtype=np.uint64)
           .astype(np.uint32) for n in heights]
    total = sum(heights)
    for n_rows in (None, total, 16):
        for _ in range(3):     # fresh buffers may reuse freed dirty memory
            out = merge_operands(ops, n_rows=n_rows)
            assert out.shape == (max(n_rows or 0, total), 4, 3)
            np.testing.assert_array_equal(out[:total], np.concatenate(ops))
            assert not out[total:].any()
            out[:] = 0xFFFFFFFF


def test_stage_counters_reach_telemetry_and_metrics():
    """Each launch's ``placements`` and ``staged_bytes`` are summed into
    the snapshot's ``dispatch`` section and the two exported counters."""
    server = CryptoServer(ServeConfig(n_c=4, max_age_s=0.005, validate=False,
                                      row_ladder_max=8,
                                      merge_dispatch=True,
                                      metrics=True),
                          coscheduler=SliceCoScheduler(row_ladder=(4, 8)))
    rng = np.random.default_rng(3)
    reqs, _ = _requests("dilithium", 64, 10, rng, tid0=0)
    for i, r in enumerate(reqs):
        server.submit(r, now=i * 1e-4)
    server.drain(0.05)
    disp = server.telemetry.snapshot()["dispatch"]
    assert disp["dispatches"] >= 2
    assert disp["placements"] == disp["dispatches"]
    assert disp["staged_bytes"] == disp["launched_rows"] * 64 * 4
    text = server.metrics_text()
    assert f"repro_stage_placements_total {disp['placements']}" in text
    assert f"repro_stage_bytes_total {disp['staged_bytes']}" in text
