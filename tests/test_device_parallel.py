"""Device-parallel fleet: env bootstrap, device pinning, partition shapes,
dispatch-overlap audit, and device-mode ≡ simulated-oracle bit parity.

The module asks for 4 forced host devices *before* jax initialises; when
another test module already initialised jax (tier-1 runs collect this file
after ``test_cluster``), the multi-device cases skip and the parity /
validation / audit cases still run on whatever device count the process
has.  The ``tier2-devices`` CI job sets ``XLA_FLAGS`` in the environment so
every case runs under a real 4-device topology.
"""
from repro.launch.xla_env import (HOST_DEVICE_FLAG, force_host_device_count,
                                  maybe_force_host_device_count,
                                  with_host_device_count)

maybe_force_host_device_count(4)   # must precede any jax-importing line

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ClusterConfig, ClusterServer
from repro.core.scheduler.coscheduler import (SliceCoScheduler,
                                              partition_devices,
                                              resolve_devices)
from repro.launch.serve import serve_crypto, serve_crypto_cluster
from repro.serve.telemetry import DispatchOverlapAuditor

N_DEV = jax.device_count()
multi = pytest.mark.skipif(N_DEV < 2, reason="needs >= 2 JAX devices")
quad = pytest.mark.skipif(N_DEV < 4, reason="needs >= 4 JAX devices")

# The parity cells mirror test_cluster's acceptance config (mixed eager/lazy
# reduction classes).  One shared oracle co-scheduler, and one pinned
# co-scheduler per *device* (hosts pinned to the same device share a
# compiled-program cache — bit-neutral, rows are what they are), keep the
# module from recompiling the engine set once per host count.
LAZY_KW = dict(accum="int32_native", d_tile=171,
               reduction_by_workload={"dilithium": "lazy"})
LAZY_COS = SliceCoScheduler(**LAZY_KW)
_PINNED_LAZY: dict = {}


def _pinned_lazy_factory(host: int) -> SliceCoScheduler:
    dev = host % N_DEV
    if dev not in _PINNED_LAZY:
        _PINNED_LAZY[dev] = SliceCoScheduler(devices=[dev], **LAZY_KW)
    return _PINNED_LAZY[dev]


# --- xla_env bootstrap ---------------------------------------------------------

def test_with_host_device_count_pure_edit():
    assert with_host_device_count(None, 4) == f"{HOST_DEVICE_FLAG}=4"
    # user flags survive; an existing count token is replaced, not stacked
    out = with_host_device_count(
        f"--xla_cpu_foo=1 {HOST_DEVICE_FLAG}=2 --xla_bar=x", 8)
    assert out.split() == ["--xla_cpu_foo=1", "--xla_bar=x",
                           f"{HOST_DEVICE_FLAG}=8"]
    with pytest.raises(ValueError):
        with_host_device_count("", 0)


def test_force_host_device_count_after_jax_init():
    jax.devices()   # ensure the backend is live
    env = {"XLA_FLAGS": "--xla_something=1"}
    # matching count: a no-op that must NOT clobber the caller's env
    force_host_device_count(N_DEV, env=env)
    assert env == {"XLA_FLAGS": "--xla_something=1"}
    with pytest.raises(RuntimeError):
        force_host_device_count(N_DEV + 1, env=env)
    # best-effort variant degrades to False instead of raising
    assert maybe_force_host_device_count(N_DEV + 1, env=env) is False
    assert env == {"XLA_FLAGS": "--xla_something=1"}


# --- devices= validation -------------------------------------------------------

def test_resolve_devices_rejects_bad_specs():
    assert resolve_devices(None) == list(jax.devices())
    assert resolve_devices([0]) == [jax.devices()[0]]
    assert resolve_devices([jax.devices()[0]]) == [jax.devices()[0]]
    with pytest.raises(ValueError, match="twice"):
        resolve_devices([0, 0])
    with pytest.raises(ValueError, match="out of range"):
        resolve_devices([N_DEV])
    with pytest.raises(ValueError, match="at least one"):
        resolve_devices([])


def test_coscheduler_devices_validation_at_construction():
    with pytest.raises(ValueError, match="twice"):
        SliceCoScheduler(devices=[0, 0])
    with pytest.raises(ValueError, match="out of range"):
        SliceCoScheduler(devices=[N_DEV + 7])


def test_default_coscheduler_is_unpinned():
    cos = SliceCoScheduler()
    assert not cos._pinned
    assert cos.devices == list(jax.devices())
    assert set(cos.device_ids()) == {d.id for d in jax.devices()}


# --- device partitioning -------------------------------------------------------

def test_partition_devices_shapes():
    with pytest.raises(ValueError):
        partition_devices(0)
    ids = [d.id for d in jax.devices()]
    # D >= n_parts: contiguous near-even chunks covering every device once
    parts = partition_devices(1)
    assert [[d.id for d in p] for p in parts] == [ids]
    if N_DEV >= 2:
        parts = partition_devices(2)
        flat = [d.id for p in parts for d in p]
        assert flat == ids and abs(len(parts[0]) - len(parts[1])) <= 1
    # D < n_parts: round-robin singletons (hosts share device queues)
    parts = partition_devices(2 * N_DEV + 1)
    assert all(len(p) == 1 for p in parts)
    assert [p[0].id for p in parts] == [ids[i % N_DEV]
                                        for i in range(2 * N_DEV + 1)]


@quad
def test_partition_four_devices_distinct():
    parts = partition_devices(4)
    assert [len(p) for p in parts] == [1, 1, 1, 1]
    assert len({p[0].id for p in parts}) == 4


# --- pinned placement ----------------------------------------------------------

@multi
def test_pinned_placement_operand_planes_and_log():
    target = jax.devices()[N_DEV - 1]
    cos = SliceCoScheduler(devices=[target.id])
    assert cos._pinned and cos.devices == [target]
    assert cos.device_ids() == (target.id,)
    # operands commit to the pinned device
    op = cos._shard("dilithium", jnp.zeros((8, 64), jnp.uint32))
    assert op.devices() == {target}
    # the engine's twiddle planes re-home onto the pin (the process-wide
    # engine cache uploads to the default device)
    planes = cos.device_planes_for("dilithium", 64)
    for leaf in jax.tree_util.tree_leaves(planes):
        assert leaf.devices() == {target}
    # and the cache returns the same re-homed pytree, not a fresh upload
    assert cos.device_planes_for("dilithium", 64) is planes
    # both workload-class meshes stay inside the pin
    for workload in ("dilithium", "bn254"):
        assert set(cos.device_ids(workload)) <= {target.id}


def test_unpinned_planes_passthrough():
    """An unpinned one-device slice passes the engine's upload through (no
    second copy); an unpinned multi-device slice places each workload
    group's planes on that group's devices once, so no launch copies them
    off the default device."""
    cos = SliceCoScheduler()
    for workload, d in (("dilithium", 64), ("bn254", 64)):
        planes = cos.device_planes_for(workload, d)
        engine_planes = cos.engine_for(workload, d).device_planes()
        group = {dev for dev in cos._meshes[workload].devices.flat}
        for a, b in zip(jax.tree_util.tree_leaves(planes),
                        jax.tree_util.tree_leaves(engine_planes)):
            if N_DEV == 1:
                assert a is b   # no re-upload, no extra device memory
            else:
                assert a.devices() == group
        assert cos.device_planes_for(workload, d) is planes


# --- cluster-layer partitioning ------------------------------------------------

def test_cluster_partitions_devices_and_reports_them():
    cluster = ClusterServer(ClusterConfig(n_hosts=4, device_parallel=True))
    snap = cluster.snapshot()
    dv = snap["devices"]
    assert dv["device_parallel"] and len(dv["per_host"]) == 4
    expect = [[d.id for d in p] for p in partition_devices(4)]
    assert dv["per_host"] == expect
    assert dv["distinct"] == min(4, N_DEV)
    assert "dispatch_overlap" in snap
    # off by default: every host sees the whole process, nothing pinned
    plain = ClusterServer(ClusterConfig(n_hosts=2)).snapshot()["devices"]
    assert not plain["device_parallel"]
    assert plain["distinct"] == N_DEV


# --- dispatch-overlap audit (pure event-order unit test) -----------------------

def test_overlap_auditor_event_order():
    aud = DispatchOverlapAuditor()
    f0, f1, f2 = object(), object(), object()
    aud.on_launch(0, f0, [{"devices": (0,)}])
    aud.on_launch(1, f1, [{"devices": (1,)}])       # disjoint device: clean
    snap = aud.snapshot()
    assert snap["cross_host_shared_launches"] == 0
    assert snap["launch_concurrency_max"] == 2      # two devices busy
    aud.on_launch(2, f2, [{"devices": (0,)}])       # host 0 still in flight
    assert aud.snapshot()["cross_host_shared_launches"] == 1
    aud.on_gather(f0)
    aud.on_gather(f1)
    aud.on_gather(f2)
    snap = aud.snapshot()
    assert snap["inflight_launches"] == 0
    assert snap["launches"] == 3 and snap["flights"] == 3
    assert snap["cross_host_queue_share"] == pytest.approx(1 / 3)
    assert snap["per_host_devices"] == {"0": [0], "1": [1], "2": [0]}


def test_overlap_auditor_reset_drops_dead_host():
    aud = DispatchOverlapAuditor()
    f0, f1 = object(), object()
    aud.on_launch(0, f0, [{"devices": (0,)}])
    aud.on_launch(1, f1, [{"devices": (1,)}])
    aud.on_reset(0)   # host 0 died mid-flight
    assert aud.snapshot()["inflight_launches"] == 1
    # a later same-device launch by another host is clean — the dead
    # host's queue entry is gone, not leaked as permanently busy
    aud.on_launch(2, object(), [{"devices": (0,)}])
    assert aud.snapshot()["cross_host_shared_launches"] == 0


# --- device mode ≡ simulated oracle (bit parity) -------------------------------

@pytest.mark.parametrize("n_hosts", [1, 2, 4])
def test_device_mode_matches_simulated_oracle(n_hosts):
    """Acceptance: pinning each host slice to its own device changes
    *where* programs run, never *what* they compute — per-tenant outputs
    are bit-for-bit the single-host offline replay's, with mixed
    eager/lazy reduction classes, for N ∈ {1, 2, 4}."""
    kw = dict(duration_s=0.01, rate_hz=1024, seed=5, d_uniform=256,
              accum="int32_native", validate=False)
    offline_results, n_ops, _ = serve_crypto(coscheduler=LAZY_COS, **kw)
    offline = {}
    for res in offline_results:
        offline.update(res.outputs)

    load, snap, _ = serve_crypto_cluster(
        hosts=n_hosts, n_c=8, max_age_s=0.002, device_parallel=True,
        coscheduler_factory=_pinned_lazy_factory, **kw)
    assert set(load.outputs) == set(offline) and n_ops == len(offline)
    for tid, row in offline.items():
        np.testing.assert_array_equal(load.outputs[tid], row)
    assert snap["drain_barrier"]["complete"]
    ov = snap["dispatch_overlap"]
    assert ov["launches"] > 0 and ov["inflight_launches"] == 0
    if n_hosts <= N_DEV:
        # hosts on distinct devices → no cross-host queue gaps, ever
        assert snap["devices"]["distinct"] == n_hosts
        assert ov["cross_host_queue_share"] == 0.0
    if n_hosts > 1 and N_DEV > 1:
        assert ov["launch_concurrency_max"] >= 1


def test_device_mode_parity_under_kill_recover():
    """PR 9's chaos plan composed with device pinning: killing a host whose
    in-flight arrays live on its *own* device must still replay losslessly
    and converge to the oracle's bits."""
    kw = dict(duration_s=0.01, rate_hz=4096, seed=0, d_uniform=64,
              validate=False)
    shared = SliceCoScheduler()
    load_sim, _, _ = serve_crypto_cluster(
        hosts=4, n_c=8, max_age_s=0.002,
        coscheduler_factory=lambda h: shared, **kw)
    load_f, snap_f, _ = serve_crypto_cluster(
        hosts=4, n_c=8, max_age_s=0.002, device_parallel=True,
        fault_plan="kill@0.5:h1,recover@0.9:h1", **kw)
    fo = snap_f["failover"]
    assert fo["lost"] == 0 and fo["limbo_pending"] == 0, fo
    assert fo["summary"]["cordons"] >= 1
    assert all(h.done() for h in load_f.handles)
    assert set(load_f.outputs) == set(load_sim.outputs)
    for tid, row in load_sim.outputs.items():
        np.testing.assert_array_equal(load_f.outputs[tid], row)
