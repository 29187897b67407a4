"""Public wrapper: (B,S,H,hd) model layout <-> (BH,S,hd) kernel layout."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .. import interpret_mode
from . import ref as _ref
from . import rwkv6 as _k


def wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
        u: jax.Array, state: jax.Array, *, chunk: int = _k.CHUNK,
        force_ref: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Chunked WKV6. r,k,v,w: (B,S,H,hd); u: (H,hd); state: (B,H,hd,hd) f32.

    Returns (y: (B,S,H,hd) f32, state_out: (B,H,hd,hd) f32). Sequence is
    right-padded to a chunk multiple (w=1, k=0 padding is exact: it leaves
    both the state and real outputs untouched).
    """
    if force_ref:
        return _ref.wkv(r, k, v, w, u, state)
    B, S, H, hd = r.shape
    pad = (-S) % chunk
    if pad:
        zer = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))  # noqa: E731
        r, k, v = zer(r), zer(k), zer(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    Sp = S + pad

    def to_bh(a):
        return jnp.moveaxis(a, 2, 1).reshape(B * H, Sp, hd).astype(jnp.float32)

    rb, kb, vb, wb = map(to_bh, (r, k, v, w))
    ub = jnp.broadcast_to(u.astype(jnp.float32)[None], (B, H, hd)
                          ).reshape(B * H, hd)
    s0 = state.reshape(B * H, hd, hd).astype(jnp.float32)
    y, sout = _k.wkv_kernel(rb, kb, vb, wb, ub, s0, chunk=chunk,
                            interpret=interpret_mode())
    y = jnp.moveaxis(y.reshape(B, H, Sp, hd), 1, 2)[:, :S]
    return y, sout.reshape(B, H, hd, hd)
