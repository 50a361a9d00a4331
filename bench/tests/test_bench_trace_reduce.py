"""The trace reduction on hand-made intervals with hand-checked numbers, on
a hand-encoded trace whose programs share an op name, and on two small
traces recorded once on a TPU v5e chip (``bench/testdata/``)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace_reduce as TRR  # noqa: E402

TESTDATA = os.path.join(ROOT, "bench", "testdata")


def op(start, dur, name="fusion", scope="", device="/device:TPU:0"):
    return TRR.Op(start, dur, name, scope, device)


@pytest.fixture
def summary():
    # window [100, 1100) ns; ops overlap at [300, 350) and one straddles
    # the window's end
    ops = [op(50, 100, "copy"),                               # 100..150
           op(200, 150, "dot", "jit(_e2e)/staging_pass_0/mxu_pointwise/"
                              "dot_general"),                 # 200..350
           op(300, 100, "fold", "jit(_e2e)/staging_pass_0/vpu_fold/rem"),
           op(600, 100, "redc", "jit(_e2e)/wzone_bn254/vpu_montgomery/and"),
           op(1000, 300, "dot", "x/mxu_pointwise_not/y")]      # 1000..1100
    spans = [(100, 500, "pump"), (150, 250, "gather"),
             (500, 800, "wait_arrival"), (800, 1100, "submit")]
    return TRR.Summary(window=(100, 1100), ops=ops, spans=spans,
                       devices=["/device:TPU:0"])


def test_busy_union_and_idle(summary):
    # union inside the window: 100..150, 200..400, 600..700, 1000..1100
    assert TRR.union_ns([(o.start, o.start + o.dur) for o in summary.ops],
                        summary.window) == 50 + 200 + 100 + 100
    assert TRR.busy_s(summary) == pytest.approx(450e-9)
    assert summary.window_s == pytest.approx(1000e-9)


def test_scope_time_matches_whole_components(summary):
    assert TRR.scope_s(summary, ["mxu_pointwise"]) == pytest.approx(150e-9)
    assert TRR.scope_s(summary, ["vpu_fold", "vpu_montgomery"]) == \
        pytest.approx(200e-9)
    assert TRR.scope_s(summary, ["vpu_fold_lazy"]) == 0.0


def test_top_ops_and_idle_gaps(summary):
    assert TRR.top_ops(summary, 2) == [["dot", pytest.approx(250e-9)],
                                       ["fold", pytest.approx(100e-9)]]
    # gaps: 150..200 (50, in gather), 400..600 (200, midpoint 500: the
    # latest-starting span open then is wait_arrival), 700..1000 (300, in
    # submit)
    gaps = TRR.idle_gaps(summary)
    assert [g[0] for g in gaps] == ["submit", "wait_arrival", "gather"]
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 200e-9, 50e-9])
    assert TRR.host_span_at(summary.spans, 90) == "host_other"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*pairs) -> bytes:
    return b"".join(_field(n, v) for n, v in pairs)


def _plane(name, line_name, t0, events, metas, stat_names=()):
    """An XPlane: one line of (metadata id, offset ns, duration ns)
    events; metas {id: (name, {stat id: str})}."""
    line = _msg((1, 1), (2, line_name), (3, t0),
                *[(4, _msg((1, mid), (2, off * 1000), (3, dur * 1000)))
                  for mid, off, dur in events])
    ev_meta = [(4, _msg((1, mid), (2, _msg(
        (1, mid), (2, name),
        *[(5, _msg((1, sid), (5, val))) for sid, val in stats.items()]))))
        for mid, (name, stats) in metas.items()]
    st_meta = [(5, _msg((1, sid), (2, _msg((1, sid), (2, sname)))))
               for sid, sname in stat_names]
    return _msg((1, 1), (2, name), (3, line), *ev_meta, *st_meta)


def test_ops_resolve_through_their_own_metadata(tmp_path):
    """Two programs each number an op ``fusion.1``; each event takes the
    scope of the metadata its own id names, not of the last one by that
    name."""
    device = _plane(
        "/device:TPU:0", "XLA Ops", 1000,
        [(1, 0, 100), (2, 200, 50), (1, 400, 10)],
        {1: ("fusion.1", {7: "jit(a)/staging_pass_0/mxu_pointwise/dot"}),
         2: ("fusion.1", {7: "jit(b)/wzone_bn254/vpu_montgomery/and"})},
        stat_names=[(7, "tf_op")])
    host = _plane("/host:CPU", "host", 1000, [(3, 0, 1000)],
                  {3: ("window", {})})
    path = tmp_path / "clash.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    s = TRR.load(str(path))
    assert s.window == (1000, 2000)
    assert [(o.start, o.dur, o.name) for o in s.ops] == [
        (1000, 100, "fusion.1"), (1200, 50, "fusion.1"),
        (1400, 10, "fusion.1")]
    assert TRR.scope_s(s, ["mxu_pointwise"]) == pytest.approx(110e-9)
    assert TRR.scope_s(s, ["vpu_montgomery"]) == pytest.approx(50e-9)


def test_recorded_chip_trace():
    """0.3 s of ``mldsa65.poisson`` at 4,096 req/s on a TPU v5 lite.  The
    numbers were recomputed by hand from the raw events (a NumPy interval
    sweep over the ``XLA Ops`` line, and the ``tf_op`` paths of the ops'
    metadata)."""
    s = TRR.load(os.path.join(TESTDATA, "mldsa65_trace.xplane.pb"))
    assert s.devices == ["/device:TPU:0"]
    assert len(s.ops) == 2780
    assert s.window == (50157667, 353019725)
    assert TRR.busy_s(s) == pytest.approx(1762765e-9)
    assert 1 - TRR.busy_s(s) / s.window_s == pytest.approx(
        1 - 1762765 / 302862058)
    assert TRR.scope_s(s, ["mxu_pointwise"]) == pytest.approx(1491888e-9)
    assert TRR.scope_s(s, ["vpu_fold"]) == pytest.approx(87260e-9)
    assert TRR.scope_s(s, ["vpu_montgomery", "vpu_fold_lazy"]) == 0.0
    names = {name for _, _, name in s.spans}
    assert {"submit", "pump", "launch", "gather", "wait_arrival"} <= names
    assert TRR.idle_gaps(s, 1)[0][0] == "wait_arrival"


def test_recorded_mixed_trace():
    """10 ms of ``mixed74.backlog`` on a TPU v5 lite, cut from a 0.25 s
    trace: the ``XLA Ops`` events inside the piece, the metadata they name
    (name and ``tf_op`` only), the host spans that overlap it, and a
    ``window`` span over it.  The cell's programs number their ops alike,
    so 411 op names stand behind two or more metadata ids.  The numbers
    were recomputed from the raw events (integer ns per event, a NumPy
    interval sweep, and each event's scope through its own metadata id)."""
    from bench import xplane as X
    path = os.path.join(TESTDATA, "mixed74_backlog_trace.xplane.pb")
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    ids: dict = {}
    for num, _, plane in X.fields(buf):
        for pn, _, v in X.fields(plane):
            if num == 1 and pn == 4:
                mid, raw = X._map_entry(v)
                ids.setdefault(X._op_meta(raw, {}).name, set()).add(mid)
    assert sum(len(v) > 1 for v in ids.values()) == 411
    s = TRR.load(path)
    assert s.devices == ["/device:TPU:0"]
    assert len(s.ops) == 3321
    assert s.window == (149201809, 159201809)
    assert TRR.busy_s(s) == pytest.approx(4942545e-9)
    assert TRR.scope_s(s, ["mxu_pointwise"]) == pytest.approx(3388251e-9)
    assert TRR.scope_s(s, ["vpu_fold"]) == pytest.approx(269615e-9)
    assert TRR.scope_s(s, ["vpu_montgomery"]) == pytest.approx(777496e-9)
    assert TRR.idle_gaps(s, 1)[0][0] == "launch"
