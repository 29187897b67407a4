"""Gradient compression for cross-pod synchronization (beyond-paper
distributed-optimization trick).

Within a pod, gradient reduction rides the fast ICI mesh; *across* pods the
link is the scarce resource. Two compressors with error feedback:

* 'bf16'  — cast f32->bf16 for the cross-pod psum (2x bytes), EF residual.
* 'int8'  — per-tensor scale + int8 all_gather, local dequant-sum (4x bytes
  at 2 pods; generalizes to k pods as k*size/4 vs size for f32 psum), EF.

Both are exact-in-expectation with error feedback: the quantization residual
is added to the *next* step's gradient, so the series of updates converges
to the uncompressed series (Karimireddy et al., 2019).

Used inside a shard_map over the 'pod' axis (launch/train.py
--cross-pod=compressed); the HLO collective bytes drop is visible in the
roofline's collective term.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp



def _quant_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_psum(grads: Any, err: Any, axis: str,
                  method: str = "int8") -> Tuple[Any, Any]:
    """Cross-pod mean of ``grads`` with error feedback. Call INSIDE a
    shard_map that has ``axis`` unreduced. Returns (synced_grads, new_err)."""
    n = jax.lax.axis_size(axis)

    def one(g, e):
        gf = g.astype(jnp.float32) + e
        if method == "bf16":
            sent = gf.astype(jnp.bfloat16)
            new_e = gf - sent.astype(jnp.float32)
            total = jax.lax.psum(sent, axis).astype(jnp.float32) / n
            return total.astype(g.dtype), new_e
        if method == "int8":
            q, scale = _quant_int8(gf)
            new_e = gf - _dequant_int8(q, scale)
            qs = jax.lax.all_gather(q, axis)          # (n, ...) int8 on wire
            ss = jax.lax.all_gather(scale, axis)      # (n,) f32 (tiny)
            total = jnp.tensordot(ss, qs.astype(jnp.float32), axes=1) / n
            return total.astype(g.dtype), new_e
        if method == "none":
            return (jax.lax.psum(gf, axis) / n).astype(g.dtype), e
        raise ValueError(f"unknown compression {method!r}")

    out = jax.tree.map(one, grads, err)
    synced = jax.tree.map(lambda t: t[0], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    new_err = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda t: isinstance(t, tuple))
    return synced, new_err


def init_error(grads_like: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                        grads_like)
