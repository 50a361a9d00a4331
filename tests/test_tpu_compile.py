"""Compiles for a described TPU v5e, without the chip.

The TPU compiler is installed next to JAX, so the served programs and the
Pallas kernels (with Mosaic, ``interpret=False``) compile here for a
``v5e:2x2`` topology that is described, not attached.  This catches what
the CPU backend and the Pallas interpreter accept but the chip's compiler
refuses: unsupported casts and matmul types, layouts Mosaic cannot lower.
Nothing runs, so results are checked on the chip (``chip_smoke.py``).

This is the only test file that describes a TPU: the topology is built
inside a fixture, never at import, so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import validator as V
from repro.core.scheduler.coscheduler import SliceCoScheduler
from repro.kernels import (fused_ntt_tile, limb_matmul, mont_fold,
                           staging_passes)

ROWS = 64   # the top row-ladder rung of the v5e serving configuration


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a TPU
    compile cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _served(workload, d, accum, reduction):
    """The co-scheduler's served program and its argument shapes."""
    cos = SliceCoScheduler(
        accum=accum, reduction_by_workload={workload: reduction},
        d_tile=171 if accum == "int32_native" else None)
    shape = cos.operand_shape(workload, d, ROWS)
    return (cos, cos.engine_for(workload, d),
            jax.ShapeDtypeStruct(shape, jnp.uint32),
            cos.engine_for(workload, d).device_planes())


@pytest.mark.parametrize("workload,d,accum,reduction", [
    ("dilithium", 256, "fp32_mantissa", "eager"),
    ("dilithium", 256, "int32_native", "eager"),
    ("dilithium", 512, "int32_native", "lazy"),
    ("bn254", 64, "fp32_mantissa", "eager"),
])
def test_served_program_compiles(one_chip, workload, d, accum, reduction):
    cos, _, operand, planes = _served(workload, d, accum, reduction)
    compiled = cos.jitted_for(workload, d).lower(
        _shapes(operand, one_chip), _shapes(planes, one_chip)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("d,accum,reduction", [
    (256, "fp32_mantissa", "eager"),
    (512, "int32_native", "lazy"),
])
def test_validator_accepts_tpu_compiled_dilithium(one_chip, d, accum,
                                                  reduction):
    _, eng, operand, planes = _served("dilithium", d, accum, reduction)

    def _e2e(x, p):
        return eng.e2e(x, planes=p)

    args = (_shapes(operand, one_chip), _shapes(planes, one_chip))
    if reduction == "eager":
        rep = V.validate_fn(_e2e, *args, expected_passes=eng.n_passes)
    else:
        rep = V.validate_fn(_e2e, *args, expect_eager=False,
                            expected_windows=eng.fold_profile["n_folds"],
                            n_diag=eng.n_diag)
    rep.raise_if_failed()
    assert rep.n_dots > 0


def _mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


PASS_CASES = [(w, accum, n) for w in ("dilithium", "bn254")
              for accum in ("int32_native", "fp32_mantissa")
              for n in (128, 3)]


@pytest.mark.parametrize("workload,accum,n", PASS_CASES)
def test_limb_matmul_compiles(one_chip, workload, accum, n):
    sp = staging_passes()[workload]
    a = jax.ShapeDtypeStruct((n, sp["k"]), jnp.uint8, sharding=one_chip)
    b = jax.ShapeDtypeStruct((sp["k"], sp["d"] * sp["n_diag"]), jnp.int8,
                             sharding=one_chip)
    _mosaic(lambda x, y: limb_matmul(x, y, accum=accum), a, b)


@pytest.mark.parametrize("workload,accum,n", PASS_CASES)
def test_fused_ntt_tile_compiles(one_chip, workload, accum, n):
    sp = staging_passes()[workload]
    a = jax.ShapeDtypeStruct((n, sp["k"]), jnp.uint8, sharding=one_chip)
    b = jax.ShapeDtypeStruct((sp["k"], sp["d"] * sp["n_diag"]), jnp.int8,
                             sharding=one_chip)
    _mosaic(lambda x, y: fused_ntt_tile(x, y, modulus=sp["modulus"],
                                        n_diag=sp["n_diag"], accum=accum),
            a, b)


@pytest.mark.parametrize("workload,n", [(w, n) for w in ("dilithium", "bn254")
                                        for n in (128, 3)])
def test_mont_fold_compiles(one_chip, workload, n):
    sp = staging_passes()[workload]
    diags = jax.ShapeDtypeStruct((n, sp["d"], sp["n_diag"]), jnp.int32,
                                 sharding=one_chip)
    _mosaic(lambda x: mont_fold(x, sp["modulus"]), diags)
