"""``correct`` must come out false when the timed path is broken.

Each test drives a whole run on the CPU (set-up, warm-up, window, the
comparison) without the harness's look for a chip, at a size a test run
holds: one ladder rung, short windows, and for the mixed configuration
degrees of 64 only.  The faults are planted under the server, in the
co-scheduler's compiled program: an answer altered where it is produced,
and half of each launch's rows left out.  The controls are the
configurations' own: the plain NTT in float32 in the program's place
(``mldsa65_ntt``) and the 9-channel BN254 class (``paper74_mixed``)."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as R  # noqa: E402

SEED = 2 ** 31 + 12345


def small(cell, degree=None, rate=400.0, traffic=None):
    """The cell's files at a test's size; ``traffic`` names another open
    mix for the cell's configuration."""
    bench, wl, cfg, mix = R.load_spec(cell)
    if traffic is not None:
        mix = R.TR.load_mix(traffic)
    cfg = copy.deepcopy(cfg)
    cfg["serving"]["row_ladder_max"] = 8
    if degree is not None:
        for c in cfg["classes"]:
            c["degree_low"] = c["degree_high"] = degree
    return bench, wl, cfg, dict(mix, request_rate_hz=rate)


class Faults:
    """Wraps the co-scheduler's compiled programs; ``mode`` picks the
    fault applied to every launch's output."""

    def __init__(self):
        self.mode = None

    def install(self, server, cos):
        import jax.numpy as jnp
        jitted_for = cos.jitted_for

        def faulty_for(workload, d):
            program = jitted_for(workload, d)

            def run(operand, planes):
                out = program(operand, planes)
                if self.mode == "altered":
                    return out.at[0, 0].add(jnp.uint32(1))
                if self.mode == "half_left_out":
                    return out.at[out.shape[0] // 2:].set(0)
                return out
            return run
        cos.jitted_for = faulty_for


@pytest.fixture(scope="module")
def mldsa():
    faults = Faults()
    sess = R.setup(*small("mldsa65.poisson"), trace=False,
                   require_tpu=False, cache=False,
                   setup_hook=faults.install)
    return sess, faults


@pytest.mark.parametrize("mode,correct", [(None, True), ("altered", False),
                                          ("half_left_out", False)])
def test_mldsa_faults_fail(mldsa, mode, correct):
    sess, faults = mldsa
    faults.mode = mode
    try:
        out = R.window(sess, SEED, 0.5, False)
    finally:
        faults.mode = None
    assert out["correct"] is correct
    assert list(out)[-1] == "checks"
    wrong = out["checks"]["dilithium_rows_wrong"]["value"]
    assert (wrong == 0) is correct


def test_mldsa_control_fails():
    sess = R.setup(*small("mldsa65.poisson"), trace=False,
                   require_tpu=False, cache=False, control=True)
    out = R.window(sess, SEED, 0.5, False)
    assert out["correct"] is False
    assert out["checks"]["dilithium_rows_wrong"]["value"] > 0


@pytest.mark.parametrize("control,correct", [(False, True), (True, False)])
def test_mixed_control_fails(control, correct):
    sess = R.setup(*small("mixed74.backlog", degree=64, rate=200.0,
                          traffic="mixed74_poisson"),
                   trace=False, require_tpu=False, cache=False,
                   control=control)
    out = R.window(sess, SEED, 0.5, False)
    assert out["correct"] is correct
    assert out["checks"]["dilithium_rows_wrong"]["value"] == 0
    assert (out["checks"]["bn254_rows_wrong"]["value"] == 0) is correct


@pytest.fixture(scope="module")
def mixed():
    faults = Faults()
    sess = R.setup(*small("mixed74.backlog", degree=64, rate=1000.0,
                          traffic="mixed74_poisson"),
                   trace=False, require_tpu=False, cache=False,
                   setup_hook=faults.install)
    return sess, faults


@pytest.mark.parametrize("mode", ["altered", "half_left_out"])
def test_mixed_faults_fail_bn254(mixed, mode):
    """Every BN254 row is compared, so a fault in the BN254 program shows
    in ``bn254_rows_wrong``."""
    sess, faults = mixed
    faults.mode = mode
    try:
        out = R.window(sess, SEED, 0.5, False)
    finally:
        faults.mode = None
    assert out["correct"] is False
    assert out["checks"]["bn254_rows_wrong"]["value"] > 0
