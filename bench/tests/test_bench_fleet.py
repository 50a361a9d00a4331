"""The fleet cell on the CPU: ``mixed74.fleet4``'s set-up and window on
four host devices, at a test's size (degree 64, one ladder rung, short
windows), without the harness's look for a chip.

Four devices exist only if XLA is told so before JAX starts, so the run
happens in a child process (this file, run as a script), which prints one
JSON line per window.  Checked: every row correct and every host launching,
warm-up reaching every host (no compile or trace in the window), and
``correct`` coming out false when an answer is altered where it is
produced, when the router's choice is not the host that answers, and under
the configuration's control.  A single-chip cell still builds one
``CryptoServer`` with one co-scheduler."""
import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness as H  # noqa: E402
from bench import run as R  # noqa: E402

CELL = "mixed74.fleet4"
SEED = 2 ** 31 + 4242


def small():
    bench, wl, cfg, mix = R.load_spec(CELL)
    cfg = copy.deepcopy(cfg)
    cfg["serving"]["row_ladder_max"] = 8
    for c in cfg["classes"]:
        c["degree_low"] = c["degree_high"] = 64
    return bench, wl, cfg, dict(mix, request_rate_hz=400.0)


def child():
    """Drive the windows and print what the parent checks."""
    import jax.numpy as jnp
    mode = {"fault": None}

    def plant(srv, cos):
        jitted_for = cos.jitted_for

        def faulty_for(workload, d):
            program = jitted_for(workload, d)

            def run(operand, planes):
                out = program(operand, planes)
                if mode["fault"] == "altered" and srv.host_id == 2:
                    return out.at[0, 0].add(jnp.uint32(1))
                return out
            return run
        cos.jitted_for = faulty_for

    sess = R.setup(*small(), trace=False, require_tpu=False, cache=False,
                   setup_hook=plant)
    server = sess.server
    host_for = server.router.host_for
    coss = H.coschedulers(server)
    print(json.dumps({
        "kind": "setup", "server": type(server).__name__,
        "devices": [sorted(c.device_ids()) for c in coss],
        "shared_programs": all(c._jitted is coss[0]._jitted for c in coss),
        "validated": [len(s._validated) for s in server.hosts],
        "warm_launches": sess.marks["warm_launches"]}), flush=True)
    for fault in (None, "altered", "misrouted"):
        mode["fault"] = fault
        if fault == "misrouted":
            server.router.host_for = lambda t: (host_for(t) + 1) % 4
        compiles = sess.compile_s.get("n_backend_compile", 0)
        traces = R._traces(coss)
        out = R.window(sess, SEED, 0.5, False)
        print(json.dumps({
            "kind": "window", "fault": fault, "correct": out["correct"],
            "failed": out["failed"], "attempted": out["attempted"],
            "checks": {k: v["value"] for k, v in out["checks"].items()},
            "by_host": sorted({rec[4] for rec in sess.probes.launches}),
            "compiles": sess.compile_s.get("n_backend_compile", 0) - compiles,
            "traces": R._traces(coss) - traces,
            "device": out["device"]}), flush=True)
    server.router.host_for = host_for
    ctl = R.setup(*small(), trace=False, require_tpu=False, cache=False,
                  control=True)
    out = R.window(ctl, SEED, 0.5, False)
    print(json.dumps({"kind": "control", "correct": out["correct"],
                      "checks": {k: v["value"]
                                 for k, v in out["checks"].items()}}),
          flush=True)


@pytest.fixture(scope="module")
def lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = [json.loads(l) for l in p.stdout.splitlines()
           if l.startswith("{")]
    return {(o["kind"], o.get("fault")): o for o in out}


def test_fleet_is_four_hosts_one_chip_each(lines):
    s = lines[("setup", None)]
    assert s["server"] == "ClusterServer"
    assert s["devices"] == [[0], [1], [2], [3]]
    # one program table and one validation record for the fleet: each
    # (class, bucket) validated once, seen as validated by every host
    assert s["shared_programs"] and s["validated"] == [2, 2, 2, 2]
    # every host made every (class, bucket, rung) launch: 2 x 1 x 1
    assert s["warm_launches"] == 4 * 2


def test_fleet_window_is_correct_on_every_host_with_nothing_compiled(lines):
    w = lines[("window", None)]
    assert w["correct"] and w["failed"] == 0 and w["attempted"] == 200
    assert all(v == 0 for v in w["checks"].values())
    assert w["by_host"] == [0, 1, 2, 3]
    assert w["compiles"] == 0 and w["traces"] == 0
    assert w["device"]["count"] == 4


@pytest.mark.parametrize("fault,check", [
    ("altered", ("dilithium_rows_wrong", "bn254_rows_wrong")),
    ("misrouted", ("answered_off_owner",))])
def test_fleet_faults_fail(lines, fault, check):
    w = lines[("window", fault)]
    assert w["correct"] is False
    assert sum(w["checks"][c] for c in check) > 0
    assert w["checks"]["unanswered"] == 0


def test_fleet_control_fails(lines):
    c = lines[("control", None)]
    assert c["correct"] is False
    assert c["checks"]["bn254_rows_wrong"] > 0
    assert c["checks"]["dilithium_rows_wrong"] == 0


def test_single_chip_cell_builds_one_server():
    import jax
    from repro.serve.server import CryptoServer
    _, _, cfg, _ = R.load_spec("mixed74.poisson")
    server = H.build_server(cfg, jax.devices()[:1])
    assert type(server) is CryptoServer
    assert H.hosts_of(server) == [server]
    assert len(H.coschedulers(server)) == 1
    assert H.owner_of(server)(12345) == 0


if __name__ == "__main__":
    child()
