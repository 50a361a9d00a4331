"""The program's phase spans as the benchmark reads them: a small traced
window on the CPU through ``bench/run.py``'s set-up and window, the
readers of the metrics drawn from the phases on hand-built traces, and the
committed chip traces, which hold no phases and reduce as they always
have."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import phase_report as REP  # noqa: E402
from bench import phase_trace as PT  # noqa: E402
from bench import run as R  # noqa: E402
from bench import trace_reduce as TRR  # noqa: E402
from repro.obs.tracing import PHASE_PREFIX, PHASES  # noqa: E402

SEED = 2 ** 31 + 777
TESTDATA = os.path.join(ROOT, "bench", "testdata")
SPAN_METRICS = ("admission.host_us_per_req", "batcher.enqueue_us_per_req",
                "server.finish_ms_per_launch", "cosched.stage_ms_per_launch")
IDLE_METRIC = "device.idle_in_server_share"


@pytest.fixture(scope="module")
def traced():
    """One traced 0.3 s window of ``mldsa65.poisson`` at a test's size."""
    bench, wl, cfg, mix = R.load_spec("mldsa65.poisson")
    cfg = copy.deepcopy(cfg)
    cfg["serving"]["row_ladder_max"] = 8
    sess = R.setup(bench, wl, cfg, dict(mix, request_rate_hz=2000.0),
                   trace=True, require_tpu=False, cache=False)
    validated = sess.server.telemetry.live["phases"]["validate"][1]
    line = REP.report(sess, "mldsa65.poisson", SEED, 0.3, True)
    path = TRR.find_xplane(PT.TRACE_DIR)
    window, spans = PT.load(path)
    return {"line": line, "window": window, "spans": spans,
            "summary": TRR.load(path), "validated": validated}


def test_every_phase_on_the_host_plane_inside_the_window(traced):
    w = traced["window"]
    inside = {name for s, e, name in traced["spans"]
              if s >= w[0] and e <= w[1]}
    # validation did its work at set-up, once per (class, bucket)
    assert inside == set(PHASES) - {"validate"}
    assert traced["validated"] == 1


def test_phase_spans_never_overlap(traced):
    spans = traced["spans"]
    assert spans
    for (_, e0, _), (s1, _, _) in zip(spans, spans[1:]):
        assert s1 >= e0


def test_trace_holds_a_span_for_every_counted_call(traced):
    w, phases = traced["window"], traced["line"]["phases"]
    for name, p in phases.items():
        n = sum(1 for s, e, nm in traced["spans"]
                if nm == name and s >= w[0] and e <= w[1])
        assert n == p["calls"], name


def test_readers_return_numbers_and_the_device_reader_none_on_cpu(traced):
    metrics = traced["line"]["metrics"]
    for name in SPAN_METRICS:
        assert metrics[name] > 0, name
    # the CPU trace has no device plane
    assert traced["summary"].devices == []
    ctx = {"trace": traced["summary"]}
    assert R.load_reader(IDLE_METRIC)(ctx) is None


def test_phase_names_differ_from_every_harness_span():
    harness = set(TRR.HOST_SPANS) | {TRR.WINDOW_SPAN}
    for name in PHASES:
        assert name not in harness
        assert PHASE_PREFIX + name not in harness
    assert PT.PREFIX == PHASE_PREFIX


def _hand_built():
    """Window [0, 1000) ns; the device busy at [100, 200) and [500, 600),
    so idle for 800 ns: [0, 100), [200, 500), [600, 1000)."""
    dev = "/device:TPU:0"
    ops = [TRR.Op(100, 100, "dot", "", dev), TRR.Op(500, 100, "dot", "", dev)]
    summary = TRR.Summary(window=(0, 1000), ops=ops, spans=[],
                          devices=[dev])
    spans = [(200, 300, "admit"), (300, 450, "stage"), (550, 700, "resolve"),
             (900, 1100, "call")]
    ctx = {"trace": summary, "n_submitted": 4,
           "telemetry": {"dispatches": 2}}
    return summary, spans, ctx


def test_idle_by_phase_adds_up_to_the_idle_time():
    summary, spans, _ = _hand_built()
    by = PT.idle_by_phase(summary, spans)
    # admit 100, stage 150, resolve 100 (600..700), call 100 (900..1000)
    assert by == pytest.approx({"admit": 100e-9, "stage": 150e-9,
                                "resolve": 100e-9, "call": 100e-9,
                                PT.NO_PHASE: 350e-9})
    assert sum(by.values()) == pytest.approx(
        summary.window_s - TRR.busy_s(summary))


def test_readers_on_a_hand_built_trace(monkeypatch):
    summary, spans, ctx = _hand_built()
    monkeypatch.setattr(PT, "window_phases", lambda c: spans)
    read = {n: R.load_reader(n)(ctx) for n in SPAN_METRICS + (IDLE_METRIC,)}
    assert read["admission.host_us_per_req"] == pytest.approx(100e-9 / 4 * 1e6)
    assert read["batcher.enqueue_us_per_req"] is None     # no such span
    assert read["server.finish_ms_per_launch"] == pytest.approx(
        150e-9 / 2 * 1e3)
    assert read["cosched.stage_ms_per_launch"] == pytest.approx(
        150e-9 / 2 * 1e3)
    assert read[IDLE_METRIC] == pytest.approx(450 / 800 * 100)
    # a program without phases: every reader reports nothing
    monkeypatch.setattr(PT, "window_phases", lambda c: [])
    for name in SPAN_METRICS + (IDLE_METRIC,):
        assert R.load_reader(name)(ctx) is None


def test_window_phases_refuses_another_runs_trace(traced):
    summary = traced["summary"]
    other = TRR.Summary(window=(summary.window[0] + 1, summary.window[1]),
                        ops=[], spans=[], devices=[])
    assert PT.window_phases({"trace": other}) is None
    assert PT.window_phases({"trace": None}) is None
    assert PT.window_phases({"trace": summary}) == traced["spans"]


@pytest.mark.parametrize("name,busy,top,gaps", [
    ("mldsa65_trace.xplane.pb", 1762765e-9,
     [("%copy.3 ", 312818e-9), ("%reshape ", 311803e-9),
      ("%slice_bitcast_fusion ", 242626e-9)],
     [("wait_arrival", 5852243e-9), ("wait_arrival", 5174028e-9),
      ("wait_arrival", 5147081e-9)]),
    ("mixed74_backlog_trace.xplane.pb", 4942545e-9,
     [("%and_bitcast_fusion.2 ", 267876e-9),
      ("%and_bitcast_fusion.5 ", 77221e-9),
      ("%and_bitcast_fusion.2 ", 71167e-9)],
     [("launch", 3782966e-9), ("launch", 985109e-9),
      ("launch", 191607e-9)]),
])
def test_committed_traces_reduce_as_before(name, busy, top, gaps):
    """The recorded chip traces hold no phase spans; the harness's own
    reduction of them is what it was before the phases existed."""
    path = os.path.join(TESTDATA, name)
    s = TRR.load(path)
    assert TRR.busy_s(s) == pytest.approx(busy)
    assert [(n.split("=")[0], t) for n, t in TRR.top_ops(s, 3)] == [
        (n, pytest.approx(t)) for n, t in top]
    assert TRR.idle_gaps(s, 3) == [[n, pytest.approx(t)] for n, t in gaps]
    window, spans = PT.load(path)
    assert window == s.window and spans == []
    assert PT.idle_by_phase(s, spans) == pytest.approx(
        {PT.NO_PHASE: s.window_s - TRR.busy_s(s)})
