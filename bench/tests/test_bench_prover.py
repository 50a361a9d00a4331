"""The prover cell's benchmark parts: the four-step launch's MACs and bytes
against a hand count, its two per-layer readers on a hand-made trace (and
on a trace without the four-step, as the parent's program gives), and the
configuration and cell as ``bench/run.py`` loads them."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import fourstep_cost as FC  # noqa: E402
from bench import roofline as RF  # noqa: E402
from bench import run as R  # noqa: E402
from bench import trace_reduce as TRR  # noqa: E402

CELL = "prover16.backlog"
DEV = "/device:TPU:0"
STAGE1 = "jit(_e2e)/wzone_bn254/pzone_4limb/fourstep_stage1/channel_0/"


def test_launch_cost_by_hand_at_64():
    """d = 64: two stages of 8 × 8, one pass each (d_tile 171 > 8); K = 8
    columns × 4 limbs = 32, M = 8 × 7 diagonals = 56, 8 rows × 8 = 64 GEMM
    rows; 18 channels."""
    c = FC.launch_cost(64, rows=8, d_tile=171)
    assert FC.split(64) == (8, 8)
    assert c["macs"] == 2 * 18 * (64 * 32 * 56)
    assert c["bytes"] == 2 * 18 * (64 * 32 + 32 * 56 + 4 * 64 * 56)


def test_launch_cost_of_the_cell():
    """d = 2^16, 8 rows: each stage is a dense 256-point launch of 2,048
    rows, in two passes of 171 and 85 columns."""
    c = FC.launch_cost(65536, rows=8, d_tile=171)
    one = RF.launch_cost("bn254_full", 256, 2048, 171)
    assert c == {"macs": 2 * one["macs"], "bytes": 2 * one["bytes"]}
    assert one["macs"] == 18 * 2048 * (171 + 85) * 4 * 256 * 7


def _ctx(ops, launches, busy_s):
    summary = TRR.Summary(window=(0, 10**9), ops=ops, spans=[],
                          devices=[DEV])
    return {"trace": summary, "busy_s": busy_s,
            "probes": types.SimpleNamespace(launches=launches),
            "cfg": {"serving": {"d_tile": 171}},
            "device_kind": "TPU v5 lite"}


def _op(start, dur, scope):
    return TRR.Op(start, dur, "fusion", STAGE1 + scope if scope else "",
                  DEV)


@pytest.fixture
def prover_ctx():
    """1 s window: GEMMs 0.2 s, folds 0.1 s, the reductions 0.3 s, the
    re-encodes 0.05 s, the twiddle 0.05 s, a transpose 0.1 s; two 8-row
    launches."""
    ms = 10**6
    ops = [_op(0, 200 * ms, "staging_pass_0/mxu_pointwise/dot"),
           _op(200 * ms, 100 * ms, "staging_pass_0/vpu_fold/rem"),
           _op(300 * ms, 300 * ms, "vpu_montgomery/and"),
           _op(600 * ms, 50 * ms, "rns_encode/dot"),
           _op(650 * ms, 50 * ms, "fourstep_twiddle/rem"),
           _op(700 * ms, 100 * ms, "fourstep_transpose/copy")]
    launches = [("bn254_prover", 65536, 8, 8, 0)] * 2
    return _ctx(ops, launches, busy_s=0.8)


def _reader(name):
    return R.load_reader(name)


def test_gemm_roofline_reader(prover_ctx):
    c = FC.launch_cost(65536, 8, 171)
    least, bound = RF.bound_s(2 * c["macs"], 2 * c["bytes"],
                              RF.peaks("TPU v5 lite"))
    # The int32 diagonals written back (4 bytes × 2,048 rows × 1,792 a
    # pass) outweigh the MACs at the int8 peak: the bound is memory.
    assert bound == "memory"
    per_pass = 2048 * 171 * 4 + 171 * 4 * 1792 + 4 * 2048 * 1792
    last = 2048 * 85 * 4 + 85 * 4 * 1792 + 4 * 2048 * 1792
    bytes_ = 2 * 2 * 18 * (per_pass + last)
    got = _reader("fourstep.gemm_roofline")(prover_ctx)
    assert got == pytest.approx(bytes_ / 819e9 / 0.2 * 100.0)
    assert got == pytest.approx(least / 0.2 * 100.0)


def test_reduce_share_reader(prover_ctx):
    got = _reader("fourstep.reduce_share")(prover_ctx)
    assert got == pytest.approx((0.1 + 0.3 + 0.05 + 0.05) / 0.8 * 100.0)


def test_reduce_share_without_a_twiddle_fusion(prover_ctx):
    """On the chip XLA fuses the twiddle product into the reductions'
    fusions, so no op carries ``fourstep_twiddle``: the share still reads."""
    ops = [o for o in prover_ctx["trace"].ops
           if "fourstep_twiddle" not in o.scope]
    ctx = dict(prover_ctx, trace=TRR.Summary(
        window=(0, 10**9), ops=ops, spans=[], devices=[DEV]))
    got = _reader("fourstep.reduce_share")(ctx)
    assert got == pytest.approx((0.1 + 0.3 + 0.05) / 0.8 * 100.0)


@pytest.mark.parametrize("name", ["fourstep.gemm_roofline",
                                  "fourstep.reduce_share"])
def test_readers_find_nothing_without_the_four_step(name):
    """A dense program's trace (no four-step scope, no prover launch), and
    an untraced run: nothing to read, and no error."""
    ms = 10**6
    dense = _ctx([TRR.Op(0, 200 * ms, "fusion",
                         "jit(_e2e)/staging_pass_0/mxu_pointwise/dot", DEV)],
                 [("bn254_full", 512, 64, 60, 0)], busy_s=0.2)
    assert _reader(name)(dense) is None
    assert _reader(name)(dict(dense, trace=None)) is None


def test_cell_loads():
    bench, wl, cfg, mix = R.load_spec(CELL)
    assert wl["chips"] == 1 and wl["config"] == "bn254_prover16"
    assert mix == {"loop": "closed", "in_flight": 32, "why": mix["why"]}
    assert [c["workload"] for c in cfg["classes"]] == ["bn254_prover"]
    assert (cfg["classes"][0]["degree_low"]
            == cfg["classes"][0]["degree_high"] == 65536)
    assert cfg["reduced"] == []
    assert cfg["serving"]["row_ladder_max"] == 8 and cfg["serving"]["n_c"] == 8
    names = lambda kind: [m["name"] for m in R.metrics_for(bench, CELL, kind)]
    assert names("end_to_end") == ["ops_per_s", "setup_s"]
    assert names("per_layer") == ["fourstep.gemm_roofline",
                                  "fourstep.reduce_share"]
    control = R.apply_control(cfg)
    assert [c["workload"] for c in control["classes"]] == ["bn254_prover9"]
    assert control["guarantees"]["fold"] == {"bn254_prover9": "eager"}
