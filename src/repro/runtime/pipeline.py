"""GPipe-style pipeline parallelism over a mesh axis (PP).

The layer stack is split into S contiguous stages; each stage's parameters
live on one device group along the ``stage`` mesh axis.  Microbatches stream
through the pipeline with ``lax.ppermute`` boundary transfers — the classic
(M + S − 1)-tick schedule with bubble fraction (S−1)/(M+S−1).

Implementation notes:
* runs inside ``jax.shard_map`` over the stage axis: every device executes the
  same program on its own stage params; activations hop stages by ppermute;
* tick t computes microbatch (t − stage_id) — inactive (bubble) ticks compute
  on garbage and are masked out of the output gather;
* forward-only here (serving / evaluation); the training path composes with
  DP/TP on the remaining mesh axes.  Used by tests on an 8-device fake mesh
  and available to the launcher via ``stage_axis="pod"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, stage_params, x_microbatches, *, mesh,
                     axis: str = "pod"):
    """stage_fn(params_stage, x) -> y; all stages shape-preserving.

    stage_params: pytree with leading axis S (== mesh axis size), sharded
    over `axis`.  x_microbatches: (M, mb, ...) replicated.  Returns (M, mb,
    ...) outputs after all S stages.
    """
    s = mesh.shape[axis]
    m = x_microbatches.shape[0]
    n_ticks = m + s - 1

    def per_device(params_stage, xs):
        # params_stage: (1, ...) local slice; xs: (M, mb, ...) replicated
        stage_id = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda p: p[0], params_stage)
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)
        outputs = jnp.zeros((m,) + mb_shape, xs.dtype)

        def tick(t, state):
            carry, outs = state
            # stage 0 ingests microbatch t; others take the permuted carry
            mb_idx = jnp.clip(t - stage_id, 0, m - 1)
            x_in = jnp.where(stage_id == 0,
                             xs[jnp.clip(t, 0, m - 1)], carry)
            y = stage_fn(params_local, x_in)
            # last stage emits microbatch (t - (S-1)) when valid
            emit = (t - (s - 1) >= 0) & (t - (s - 1) < m) & (stage_id == s - 1)
            out_idx = jnp.clip(t - (s - 1), 0, m - 1)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0),
                lambda o: o, outs)
            # hop: stage i -> stage i+1 (ring permute; last wraps, ignored)
            carry = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return carry, outs

        _, outputs = jax.lax.fori_loop(0, n_ticks, tick,
                                       (carry_in, outputs))
        # gather the last stage's outputs to everyone
        outputs = jax.lax.psum(
            jnp.where(stage_id == s - 1, outputs, jnp.zeros_like(outputs)),
            axis)
        return outputs

    other = tuple(a for a in mesh.axis_names if a != axis)
    fn = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(), check_vma=False)
    return fn(stage_params, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
