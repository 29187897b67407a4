"""Counter-based PRNG for the GA generation megakernel (Threefry-2x32).

The generation kernel draws randomness *on chip*: no precomputed noise
tensors travel HBM->VMEM, and every draw site is a pure function of
``(key, salt, counter)`` — the kernel and its jnp oracle consume the same
bits, which is what makes the jnp<->pallas(interpret) parity tests
bit-exact for binary genomes.

Implementation: the standard 20-round Threefry-2x32 block cipher (Salmon
et al., SC'11 — the same family jax.random uses) written in pure
``jnp`` uint32 ops (wrapping add / xor / rotate), so the *identical* code
runs inside a Pallas kernel body and in ordinary traced jax. The derived
distributions (uniform / randint / bernoulli / normal) are defined here
once; they intentionally favour kernel-friendly ops (24-bit uniforms via
integer convert, modulo randint, Box-Muller normals) over matching
``jax.random``'s exact bit recipes — the oracle is this module, not
jax.random.

All helpers take 2-D ``shape``s: TPU iota must be >= 2-D, and every draw
site in the generation kernel is naturally (rows, cols). Streams are
separated by a caller-chosen ``salt`` placed in the second counter word;
distinct salts give independent streams for the same key.

**Tiling-invariant counters.** Every draw accepts an optional global
``offset=(row0, col0)`` and ``row_stride``: the counter for local element
``(r, c)`` is ``(row0 + r) * row_stride + (col0 + c)`` in wrapping uint32
arithmetic (``row_stride`` defaults to the local column count, which
reproduces the legacy whole-array counters). A grid-tiled kernel that
passes its tile origin as the offset and the *global* row stride therefore
draws bit-identical randomness to a single-tile kernel drawing the whole
array at once — this is the re-keying contract that makes the tiled
generation megakernel (:mod:`.tiling`) bit-exact against the untiled one
and the jnp oracle for any tile size. Offsets may be traced (they come
from ``pl.program_id``) and may be negative (two's-complement wrap is part
of the contract and identical in jnp and Mosaic).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Threefry-2x32 rotation schedule (8 constants, reused over 20 rounds).
_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA  # key-schedule parity constant

u32 = jnp.uint32


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << u32(r)) | (x >> u32(32 - r))


def threefry2x32(k0: jax.Array, k1: jax.Array, x0: jax.Array,
                 x1: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """20-round Threefry-2x32: encrypt counter block (x0, x1) under (k0, k1).

    All inputs uint32 (scalars broadcast); returns two uint32 arrays of the
    broadcast shape. Pure wrapping uint32 arithmetic — safe inside Pallas.
    """
    k0 = jnp.asarray(k0, u32)
    k1 = jnp.asarray(k1, u32)
    x0 = jnp.asarray(x0, u32)
    x1 = jnp.asarray(x1, u32)
    ks = (k0, k1, k0 ^ k1 ^ u32(_PARITY))

    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for block in range(5):
        rots = _ROTATIONS[:4] if block % 2 == 0 else _ROTATIONS[4:]
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + u32(block + 1)
    return x0, x1


def _counters(shape: Tuple[int, int], offset=(0, 0),
              row_stride: int | None = None) -> jax.Array:
    """Counter grid for a 2-D draw (TPU-safe broadcasted iota).

    Counter of local element (r, c) = (row0 + r) * row_stride + (col0 + c)
    in wrapping uint32; defaults reproduce the legacy whole-array linear
    counters (offset (0, 0), stride = shape[1])."""
    assert len(shape) == 2, f"prng draws must be 2-D, got {shape}"
    row0, col0 = offset
    stride = shape[1] if row_stride is None else row_stride
    rows = jax.lax.broadcasted_iota(u32, shape, 0)
    cols = jax.lax.broadcasted_iota(u32, shape, 1)
    rows = rows + jnp.asarray(row0, jnp.int32).astype(u32)
    cols = cols + jnp.asarray(col0, jnp.int32).astype(u32)
    return rows * jnp.asarray(stride, jnp.int32).astype(u32) + cols


def random_bits(k0: jax.Array, k1: jax.Array, shape: Tuple[int, int],
                salt: int, offset=(0, 0),
                row_stride: int | None = None) -> jax.Array:
    """(shape) uint32 of fresh bits for stream ``salt`` under key (k0, k1)."""
    cnt = _counters(shape, offset, row_stride)
    out, _ = threefry2x32(k0, k1, cnt, jnp.full(shape, salt, u32))
    return out


def _unit24(bits: jax.Array) -> jax.Array:
    """Top 24 bits as f32 in [0, 1). The detour through int32 is exact (24
    bits fit) and is what Mosaic lowers: it refuses uint32 -> float32."""
    return ((bits >> u32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def uniform(k0, k1, shape, salt, offset=(0, 0), row_stride=None) -> jax.Array:
    """f32 uniforms in [0, 1): top 24 bits scaled — exact in float32."""
    return _unit24(random_bits(k0, k1, shape, salt, offset, row_stride))


def randint(k0, k1, shape, maxval, salt, offset=(0, 0),
            row_stride=None) -> jax.Array:
    """int32 in [0, maxval) (maxval may be traced; tiny modulo bias is part
    of this RNG's contract and shared by kernel + oracle)."""
    bits = random_bits(k0, k1, shape, salt, offset, row_stride)
    return (bits % jnp.asarray(maxval, u32)).astype(jnp.int32)


def bernoulli(k0, k1, shape, p, salt, offset=(0, 0),
              row_stride=None) -> jax.Array:
    return uniform(k0, k1, shape, salt, offset, row_stride) < jnp.float32(p)


def normal(k0, k1, shape, salt, offset=(0, 0), row_stride=None) -> jax.Array:
    """Standard normals via Box-Muller (both counter words of one call)."""
    cnt = _counters(shape, offset, row_stride)
    b0, b1 = threefry2x32(k0, k1, cnt, jnp.full(shape, salt, u32))
    u1 = _unit24(b0)
    u2 = _unit24(b1)
    r = jnp.sqrt(-2.0 * jnp.log(1.0 - u1))  # 1-u1 in (0,1]: log is finite
    return r * jnp.cos(jnp.float32(2.0 * jnp.pi) * u2)
