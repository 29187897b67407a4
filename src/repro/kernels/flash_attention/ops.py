"""Public wrapper: model layout (B,S,H,hd) -> kernel layout, padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import interpret_mode
from . import flash_attention as _k
from . import ref as _ref


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float = 1.0,
                    bq: int = _k.BQ, bk: int = _k.BK,
                    force_ref: bool = False) -> jax.Array:
    """q: (B,Sq,H,hd), k/v: (B,Sk,Kv,hd) -> (B,Sq,H,hd)."""
    if force_ref:
        return _ref.attention(q, k, v, causal=causal, scale=scale)
    B, Sq, H, hd = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded KV rows must never win the softmax: zero k gives score 0,
        # which can beat NEG-masked rows only if everything is masked —
        # causal q>=0 always sees k0, and non-causal sees all, so safe;
        # still, mask via huge negative bias by padding k with 0 and
        # relying on the causal/frontier mask to exclude them:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if pad_k and not causal:
        # non-causal path cannot mask pads inside the kernel -> fall back
        return _ref.attention(q, k, v, causal=causal, scale=scale)
    out = _k.flash_attention_kernel(qt, kt, vt, scale=scale, causal=causal,
                                    bq=bq, bk=bk, interpret=interpret_mode())
    out = jnp.moveaxis(out, 1, 2)
    return out[:, :Sq]
