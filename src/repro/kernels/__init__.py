"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships three modules:
    <name>.py  — the pl.pallas_call kernel with explicit BlockSpec tiling
    ops.py     — the jit'd public wrapper (interpret mode on the CPU only)
    ref.py     — the pure-jnp oracle the kernel is tested against

Kernels: trap (bitstring fitness), rastrigin (CEC2010-F15 fused fitness),
rwkv6 (chunked WKV linear recurrence), flash_attention (causal online-
softmax attention), ga (the evolution-kernel engine: fused
selection->crossover->mutation[->fitness] generation megakernels behind
the (op, genome_kind, impl) operator registry — selected per experiment
via ``EAConfig.impl``; ships its own counter-based Threefry RNG so the
jnp oracle and the kernel consume identical random streams).
"""


def ordered_sum(x):
    """Sum over the last axis in one fixed association: halve while the
    width is even, then add the remaining columns left to right. XLA (CPU
    or TPU) and Mosaic each perform exactly these adds, so the result is
    bit-identical across them; ``jnp.sum`` leaves the order to the
    backend, and sums of inexact values (trap's thirds) then differ in the
    last bit between a kernel and its oracle."""
    w = x.shape[-1]
    while w > 1 and w % 2 == 0:
        w //= 2
        x = x[..., :w] + x[..., w:]
    acc = x[..., 0]
    for t in range(1, w):
        acc = acc + x[..., t]
    return acc


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend
    (tests, CI) they do; on the TPU they compile. Any other backend is an
    error, never a silent fall back to the interpreter."""
    import jax

    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels here run on the TPU, or in "
                           f"interpret mode on the CPU; not on {backend!r}")
    return backend == "cpu"
