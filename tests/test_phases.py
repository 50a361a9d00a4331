"""Leaf phase spans and per-class wait counters of the serving path.

- every phase a server run passes through is counted once per call, in the
  telemetry's live record and its snapshot, and lands on the JAX
  profiler's host plane as ``repro.<phase>``;
- with the request tracer on, the phases are balanced ``B``/``E`` pairs on
  their own track of a valid Chrome trace;
- the per-class waits (admission to close, close to launch, launch to
  resolve) and the queue wait (admission to launch) are exact on a virtual
  clock, in the synchronous and the pipelined dispatch;
- cluster snapshots merge both records.
"""
import glob

import jax
import numpy as np
import pytest

from repro.cluster.telemetry import merge_snapshots
from repro.core import field as F
from repro.core.scheduler import TenantRequest
from repro.core.scheduler.coscheduler import SliceCoScheduler
from repro.obs import Phases, chrome_trace, validate_chrome_trace
from repro.obs.tracing import PHASE_PREFIX, PHASES
from repro.serve import CryptoServer, ServeConfig

RNG = np.random.default_rng(13)
COS = SliceCoScheduler()


def _req(tid, d=64):
    coeffs = np.asarray(RNG.integers(0, F.DILITHIUM_Q, d, dtype=np.uint64),
                        np.uint32)
    return TenantRequest(tid, "dilithium", d, 0.0, coeffs)


def _server(**kw):
    kw.setdefault("validate", False)
    kw.setdefault("n_c", 4)
    kw.setdefault("max_age_s", 0.005)
    return CryptoServer(ServeConfig(**kw), coscheduler=COS)


def _waits(server, stage):
    return server.telemetry.live["waits"]["dilithium"][stage]


def test_phases_record_calls_and_longest():
    ph = Phases()
    assert set(ph.record) == set(PHASES)
    for _ in range(3):
        with ph.stage:
            pass
    secs, calls, longest = ph.record["stage"]
    assert calls == 3 and 0.0 <= longest <= secs
    assert ph.record["call"] == [0.0, 0, 0.0]


def test_waits_exact_on_a_virtual_clock_pipelined():
    """Three requests pool in one batch, close by age at 5 ms, launch in
    the same event, and resolve at the next event (8 ms)."""
    srv = _server(async_pipeline=True)
    srv.submit_many([_req(0), _req(1)], now=0.000)
    srv.submit_many([_req(2)], now=0.001)
    srv.pump(0.005)
    assert srv.inflight_groups == 1
    srv.pump(0.008)
    assert srv.inflight_groups == 0
    assert _waits(srv, "to_close") == pytest.approx([0.014, 3, 0.005])
    assert _waits(srv, "to_launch") == pytest.approx([0.0, 3, 0.0])
    assert _waits(srv, "to_resolve") == pytest.approx([0.009, 3, 0.003])
    # queue wait is admission to launch, not the whole latency
    assert sorted(srv.telemetry.queue_wait.samples) == pytest.approx(
        [0.004, 0.005, 0.005])
    assert srv.telemetry.latency.percentile(100) > 0.005
    snap = srv.telemetry.snapshot()["waits"]["dilithium"]
    assert snap["to_resolve"]["mean_s"] == pytest.approx(0.003)
    assert snap["to_close"]["longest_s"] == pytest.approx(0.005)


def test_waits_exact_on_a_virtual_clock_synchronous():
    """A full batch closes at its fourth request's clock (13 ms) and is
    launched at the arrival batch's last clock (20 ms)."""
    srv = _server()
    srv.submit_many([_req(k) for k in range(5)],
                    nows=[0.010, 0.011, 0.012, 0.013, 0.020])
    assert _waits(srv, "to_close") == pytest.approx([0.006, 4, 0.003])
    assert _waits(srv, "to_launch") == pytest.approx([0.028, 4, 0.007])
    assert _waits(srv, "to_resolve") == pytest.approx([0.0, 4, 0.0])
    assert sorted(srv.telemetry.queue_wait.samples) == pytest.approx(
        [0.007, 0.008, 0.009, 0.010])
    srv.drain(0.030)
    assert _waits(srv, "to_close")[1] == 5
    srv.telemetry.reset_longest()
    assert _waits(srv, "to_launch")[2] == 0.0
    assert _waits(srv, "to_launch")[0] == pytest.approx(0.028)


def test_server_phases_counted_once_per_call():
    srv = _server(async_pipeline=True, validate=True)
    srv.submit_many([_req(k) for k in range(4)], now=0.0)   # closes full
    srv.pump(0.001)                                         # gathers
    srv.drain(0.002)
    live = srv.telemetry.live["phases"]
    assert set(live) == set(PHASES)
    assert all(live[p][1] > 0 for p in PHASES)
    assert live["admit"][1] == 1
    assert live["enqueue"][1] == 3          # submit_many, pump, drain
    assert live["stage"][1] == live["call"][1] == live["d2h"][1] == 1
    assert live["account"][1] == live["resolve"][1] == 1
    # validation does its work once per (class, bucket) and server
    assert live["validate"][1] == 1
    snap = srv.telemetry.snapshot()["phases"]
    assert snap["admit"]["calls"] == 1
    assert all(p["longest_s"] <= p["seconds"] for p in snap.values())


def test_phases_on_the_profiler_host_plane(tmp_path):
    srv = _server(async_pipeline=True)
    srv.submit_many([_req(0)], now=0.0)                     # warm the path
    srv.drain(0.001)
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv = _server(async_pipeline=True)
        srv.submit_many([_req(k) for k in range(4)], now=0.0)
        srv.pump(0.001)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    planes = jax.profiler.ProfileData.from_file(path).planes
    names = [ev.name for plane in planes for line in plane.lines
             for ev in line.events if ev.name.startswith(PHASE_PREFIX)]
    assert sorted(set(names)) == sorted(
        PHASE_PREFIX + p for p in ("admit", "enqueue", "stage", "call",
                                   "d2h", "account", "resolve"))


def test_phases_are_balanced_spans_of_the_chrome_trace():
    srv = _server(async_pipeline=True, tracing=True)
    srv.submit_many([_req(k) for k in range(6)], now=0.0)
    srv.pump(0.006)
    srv.drain(0.010)
    events = srv.trace_events()
    phase_evs = [e for e in events if e["track"] == "phases"]
    assert {e["ph"] for e in phase_evs} == {"B", "E"}
    assert {e["name"] for e in phase_evs} >= {"admit", "enqueue", "stage",
                                             "call", "d2h", "account",
                                             "resolve"}
    stats = validate_chrome_trace(chrome_trace(events))
    assert stats["requests"] == 6


def test_cluster_merge_sums_phases_and_waits():
    a, b = _server(), _server()
    a.submit_many([_req(k) for k in range(4)], now=0.0)
    b.submit_many([_req(k) for k in range(4, 12)], now=0.0)
    sa, sb = a.telemetry.snapshot(), b.telemetry.snapshot()
    merged = merge_snapshots([sa, sb])
    assert merged["phases"]["admit"]["calls"] == 2
    assert merged["phases"]["stage"]["calls"] == (
        sa["phases"]["stage"]["calls"] + sb["phases"]["stage"]["calls"])
    w = merged["waits"]["dilithium"]["to_close"]
    assert w["requests"] == 12
    assert w["mean_s"] == pytest.approx(w["seconds"] / 12)
