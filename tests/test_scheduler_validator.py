"""Two-tier scheduler + HLO validator: isolation isomorphism, metrics, zones."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import field as F
from repro.core import limb_gemm as G
from repro.core import ntt as NTT
from repro.core import validator as V
from repro.core import workloads as WK
from repro.core.scheduler import (IngressQueue, PoissonTrace, TenantRequest,
                                  RectangularScheduler, packing_metrics)
from repro.core.scheduler.rectangular import (block_diagonal_zero_fraction,
                                              bucket_degree)
from repro.core.scheduler.coscheduler import SliceCoScheduler

RNG = np.random.default_rng(0)


# --- Tier 1: rectangular scheduling -------------------------------------------

def _dil_request(tid, d):
    coeffs = np.asarray(RNG.integers(0, F.DILITHIUM_Q, d, dtype=np.uint64),
                        np.uint32)
    return TenantRequest(tid, "dilithium", d, 0.0, coeffs)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(1, 256), min_size=1, max_size=12))
def test_batched_isomorphic_to_isolated(degrees):
    """Property 5.1 / Data Correctness: row i of the batched output equals the
    isolated evaluation of tenant i's polynomial (zero-padded)."""
    sched = RectangularScheduler(n_c=8, bucket_granularity=64)
    reqs = [_dil_request(i, d) for i, d in enumerate(degrees)]
    batches = sched.plan_batches(reqs)
    assert sum(b.n_c for b in batches) == len(reqs)
    for batch in batches:
        eng = WK.DilithiumEngine(batch.d_bucket)
        out = np.asarray(eng.evaluate(jnp.asarray(batch.operand)))
        routed = sched.unstack(batch, out)
        for r in batch.requests:
            iso = np.zeros((1, batch.d_bucket), np.uint32)
            iso[0, : r.degree] = r.coeffs
            want = eng.oracle_np(iso)[0]
            np.testing.assert_array_equal(routed[r.tenant_id], want)


def test_packing_metrics_paper_values():
    # Uniform BN254 d=256 (d_max=128): fill 100%, waste 0%, staging 50%
    m = packing_metrics([256] * 8, 256, 128)
    assert m.batch_fill == 1.0 and m.padding_waste == 0.0
    assert m.staging_overhead == 0.5
    assert m.m_occupancy == 8 / 128  # the paper's 6.25% M-dim occupancy
    # Uniform Dilithium d=256 (d_max=171): footprint 342 → ~25% waste
    m = packing_metrics([256] * 8, 256, 171)
    assert abs(m.padding_waste - (342 - 256) / 342) < 1e-9
    assert m.staging_overhead == 0.5


def test_block_diagonal_waste_eliminated():
    degrees = [64, 128, 256, 512]
    bd = block_diagonal_zero_fraction(degrees)
    assert bd > 0.6  # block-diagonal wastes most of the array
    m = packing_metrics(degrees, 512, 128)
    assert m.padding_waste < bd  # rectangular stacking strictly better


def test_bucket_degree():
    assert bucket_degree(1) == 64
    assert bucket_degree(64) == 64
    assert bucket_degree(65) == 128
    assert bucket_degree(512) == 512


# --- ingress + traces ----------------------------------------------------------

def test_poisson_trace_mixture():
    trace = PoissonTrace(rate_hz=2048, duration_s=2.0, seed=1).generate()
    assert 3000 < len(trace) < 5200
    frac_dil = np.mean([r.workload == "dilithium" for r in trace])
    assert 0.45 < frac_dil < 0.55
    q = IngressQueue()
    q.push_trace(trace)
    assert set(q.workloads) == {"dilithium", "bn254"}
    batch = q.pop_batch("dilithium", 8)
    assert len(batch) == 8 and all(r.workload == "dilithium" for r in batch)


# --- Tier 2: co-scheduler ------------------------------------------------------

def test_coscheduler_dispatch_dilithium():
    sched = RectangularScheduler(n_c=4, bucket_granularity=256)
    reqs = [_dil_request(i, 256) for i in range(4)]
    batches = sched.plan_batches(reqs)
    cos = SliceCoScheduler()
    res = cos.dispatch(batches[0])
    eng = cos.engine_for("dilithium", 256)
    for r in reqs:
        want = eng.oracle_np(r.coeffs[None, :])[0]
        np.testing.assert_array_equal(res.outputs[r.tenant_id], want)


def test_coscheduler_compiles_once_per_class():
    """Repeated dispatches of one (workload, d_bucket, shape) class must hit
    the cached executable — the trace counter increments only on retrace."""
    cos = SliceCoScheduler()
    sched = RectangularScheduler(n_c=2, bucket_granularity=64)
    for round_i in range(3):                  # fresh batch objects each round
        reqs = [_dil_request(10 * round_i + i, 64) for i in range(2)]
        cos.dispatch(sched.plan_batches(reqs)[0])
    assert cos.trace_counts[("dilithium", 64)] == 1
    # a different operand shape is a legitimate retrace
    one = sched.plan_batches([_dil_request(99, 64)])[0]
    cos.dispatch(one)
    assert cos.trace_counts[("dilithium", 64)] == 2


def test_dispatch_mixed_order_and_nonblocking(monkeypatch):
    """dispatch_mixed preserves input batch order and launches every program
    before materialising any result (no host sync between launches)."""
    rng = np.random.default_rng(17)
    cos = SliceCoScheduler()
    dil = [_dil_request(i, 256) for i in range(2)]
    eng_b = cos.engine_for("bn254", 64)
    coeffs = np.array([int.from_bytes(rng.bytes(16), "little")
                       for _ in range(64)], object)
    bn = [TenantRequest(200, "bn254", 64, 0.0, np.asarray(eng_b.ingest(coeffs)))]
    sched = RectangularScheduler(n_c=2, bucket_granularity=64)
    batches = sched.plan_batches(dil + bn)
    assert len(batches) == 2

    events = []
    orig_launch = SliceCoScheduler._launch
    orig_mat = SliceCoScheduler._materialise
    monkeypatch.setattr(SliceCoScheduler, "_launch",
                        lambda self, b: (events.append("launch"),
                                         orig_launch(self, b))[1])
    monkeypatch.setattr(SliceCoScheduler, "_materialise",
                        lambda self, *f: (events.append("materialise"),
                                          orig_mat(self, *f))[1])
    results = cos.dispatch_mixed(batches)
    assert events == ["launch", "launch", "materialise", "materialise"]
    assert [r.batch is b for r, b in zip(results, batches)] == [True, True]
    # and the rows are still correct end-to-end
    for r, b in zip(results, batches):
        if b.workload != "dilithium":
            continue
        eng = cos.engine_for("dilithium", b.d_bucket)
        for req in b.requests:
            iso = np.zeros((1, b.d_bucket), np.uint32)
            iso[0, : req.degree] = req.coeffs
            np.testing.assert_array_equal(r.outputs[req.tenant_id],
                                          eng.oracle_np(iso)[0])


def test_coscheduler_rejects_bad_reduction():
    """A typo'd reduction mode must fail construction, not silently trace
    the eager path (the string used to pass through unvalidated)."""
    with pytest.raises(ValueError, match="unknown reduction mode"):
        SliceCoScheduler(reduction="lzay")
    with pytest.raises(ValueError, match="unknown reduction mode"):
        SliceCoScheduler(reduction_by_workload={"dilithium": "Lazy"})
    with pytest.raises(ValueError, match="unknown workload class"):
        SliceCoScheduler(reduction_by_workload={"dilithum": "lazy"})
    # engines and the raw transform guard the same surface
    with pytest.raises(ValueError, match="unknown reduction mode"):
        WK.DilithiumEngine(64, reduction="eagr")
    cos = SliceCoScheduler(reduction="lazy",
                           reduction_by_workload={"bn254": "eager"})
    assert cos.reduction_for("dilithium") == "lazy"
    assert cos.reduction_for("bn254") == "eager"


def test_coscheduler_mixed_dispatch():
    rng = np.random.default_rng(9)
    cos = SliceCoScheduler()
    dil = [_dil_request(i, 256) for i in range(2)]
    eng_b = cos.engine_for("bn254", 64)
    bn_reqs = []
    for i in range(2):
        coeffs = np.array([int.from_bytes(rng.bytes(16), "little")
                           for _ in range(64)], object)
        res = np.asarray(eng_b.ingest(coeffs))
        bn_reqs.append(TenantRequest(100 + i, "bn254", 64, 0.0, res))
    sched = RectangularScheduler(n_c=2, bucket_granularity=64)
    batches = sched.plan_batches(dil + bn_reqs)
    results = cos.dispatch_mixed(batches)
    assert {b.batch.workload for b in results} == {"dilithium", "bn254"}


# --- HLO validator -------------------------------------------------------------

def _staged_fn(plan, reduction="eager", barriers=True):
    def fn(a):
        y, _ = G.staged_transform(a, plan, reduction=reduction,
                                  barriers=barriers)
        return y
    return fn


@pytest.fixture(scope="module")
def dil_plan_512():
    w = NTT.ntt_matrix(512, F.DILITHIUM_Q, negacyclic=True)
    return G.make_channel_plan(w, F.DILITHIUM_Q, data_limbs=3, tw_limbs=3)


def test_validator_accepts_eager(dil_plan_512):
    a = jnp.zeros((8, 512), jnp.uint32)
    rep = V.validate_fn(_staged_fn(dil_plan_512), a, expected_passes=3)
    rep.raise_if_failed()
    assert rep.n_barriers >= 2
    assert rep.zones == set() or all(z.startswith("wzone") for z in rep.zones)


def test_validator_flags_missing_barriers(dil_plan_512):
    a = jnp.zeros((8, 512), jnp.uint32)
    rep = V.validate_fn(_staged_fn(dil_plan_512, barriers=False), a,
                        expected_passes=3)
    assert not rep.ok
    assert any(v[0] == "V2" for v in rep.violations)
    with pytest.raises(V.ValidationError):
        rep.raise_if_failed()


def test_validator_flags_cross_zone_fusion():
    """XLA happily fuses elementwise chains across zones — the class of
    cross-tensor optimisation the validator must catch (paper §6.3)."""
    def fn(x):
        with jax.named_scope("wzone_dilithium"):
            a = x * jnp.float32(2.0) + jnp.float32(1.0)
        with jax.named_scope("wzone_bn254"):
            b = x * jnp.float32(3.0) - jnp.float32(4.0)
        return a + b  # cross-zone combine → fusion mixes zones

    x = jnp.zeros((256, 256), jnp.float32)
    rep = V.validate_fn(fn, x, expect_eager=False)
    assert not rep.ok and any(v[0] == "V3" for v in rep.violations)


def test_validator_accepts_zone_separated_engines():
    """Our co-scheduled program with explicit barriers between zones passes."""
    eng_d = WK.DilithiumEngine(256)

    def fn(a, b):
        y1 = eng_d.evaluate(a)
        y1, b = jax.lax.optimization_barrier((y1, b))
        with jax.named_scope("wzone_bn254"), jax.named_scope("pzone_4limb"):
            y2 = b * jnp.uint32(2)
        return y1, y2

    a = jnp.zeros((4, 256), jnp.uint32)
    b = jnp.zeros((4, 256), jnp.uint32)
    rep = V.validate_fn(fn, a, b, expected_passes=2)
    rep.raise_if_failed()
    assert "wzone_dilithium" in rep.zones and "wzone_bn254" in rep.zones


def test_fold_census_kappa():
    """Static fold census: eager folds per pass vs one lazy fold — the κ
    amortisation object (paper §7.2.1)."""
    m, d = F.DILITHIUM_Q, 512
    w = NTT.ntt_matrix(d, m, negacyclic=True)
    eager_plan = G.make_channel_plan(w, m, data_limbs=3, tw_limbs=3)
    lazy_plan = G.make_channel_plan(w, m, data_limbs=3, tw_limbs=3,
                                    accum="int32_native")
    a = jnp.zeros((4, d), jnp.uint32)
    c_eager = V.fold_census(_staged_fn(eager_plan), a)
    def lazy_fn(x):
        y, _ = G.staged_transform(x, lazy_plan, reduction="lazy", d_max=171)
        return y
    c_lazy = V.fold_census(lazy_fn, a)
    assert c_eager["n_fold_scopes"] > c_lazy["n_fold_scopes"] >= 0


# --- V1/V2 keyed by transform stage (four-step programs) -----------------------

STAGE_PASSES = {"fourstep_stage1": 3, "fourstep_stage2": 3}


def _two_stage_fn(plan, *, stage2_barriers=True):
    """Two staged transforms of one channel, as a four-step program nests
    them: each under its ``fourstep_stage{s}`` scope."""
    def fn(a):
        with jax.named_scope("fourstep_stage1"):
            y, _ = G.staged_transform(a, plan)
        with jax.named_scope("fourstep_stage2"):
            z, _ = G.staged_transform(y, plan, barriers=stage2_barriers)
        return z
    return fn


def test_validator_accepts_two_stage_program(dil_plan_512):
    a = jnp.zeros((8, 512), jnp.uint32)
    rep = V.validate_fn(_two_stage_fn(dil_plan_512), a,
                        expected_passes=STAGE_PASSES)
    rep.raise_if_failed()


def test_validator_counts_barriers_per_stage(dil_plan_512):
    """Stage 2 without barriers: the module still holds stage 1's two, as
    many as one 3-pass transform needs, but stage 2 has none."""
    a = jnp.zeros((8, 512), jnp.uint32)
    rep = V.validate_fn(_two_stage_fn(dil_plan_512, stage2_barriers=False),
                        a, expected_passes=STAGE_PASSES)
    assert [v[0] for v in rep.violations] == ["V2"]
    assert "fourstep_stage2" in rep.violations[0][1]


@pytest.mark.parametrize("moved", [False, True])
def test_validator_flags_fold_moved_across_stage_boundary(dil_plan_512,
                                                          moved):
    """Stage 1's pass-0 fold emitted after stage 2's pass-0 summation: both
    windows are ``staging_pass_0`` of one channel, told apart only by
    their stage."""
    plan = dil_plan_512
    m = jnp.uint32(plan.modulus)
    w_tile = jnp.asarray(plan.fused_operand[:171 * plan.data_limbs])

    def fn(a):
        def summation(stage):
            with jax.named_scope(stage), jax.named_scope("staging_pass_0"):
                return G.tile_diagonals(a[:, :171], None, w_tile, plan)

        def fold(stage, diag):
            with jax.named_scope(stage), jax.named_scope("staging_pass_0"), \
                    jax.named_scope("vpu_fold"):
                return F.fold_diagonals_u32(diag, m)

        d1 = summation("fourstep_stage1")
        if not moved:
            y1 = fold("fourstep_stage1", d1)
        d2 = summation("fourstep_stage2")
        if moved:
            y1 = fold("fourstep_stage1", d1)
        return y1, fold("fourstep_stage2", d2)

    rep = V.validate_fn(fn, jnp.zeros((8, 512), jnp.uint32),
                        expected_passes={"fourstep_stage1": 1,
                                         "fourstep_stage2": 1})
    if not moved:
        rep.raise_if_failed()
        return
    assert [v[0] for v in rep.violations] == ["V1"]
    assert "fourstep_stage1/staging_pass_0→fourstep_stage2" in \
        rep.violations[0][1]
    with pytest.raises(V.ValidationError):
        rep.raise_if_failed()


def test_validator_accepts_four_step_engine():
    """The served four-step program: V1 over both stages' windows, V2 per
    stage, one workload and precision zone."""
    eng = WK.make_engine("bn254_prover", 64, accum="int32_native",
                         d_tile=171)
    a = jnp.zeros((2, 64, eng.n_channels), jnp.uint32)
    rep = V.validate_fn(lambda x, pl: eng.e2e(x, planes=pl), a,
                        eng.device_planes(),
                        expected_passes=eng.fold_profile["stage_passes"])
    rep.raise_if_failed()
    assert rep.zones == {"wzone_bn254"}
    assert rep.precision_zones == {"pzone_4limb"}
