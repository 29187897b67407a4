"""Operator-kernel registry — the engine's fifth axis (``EAConfig.impl``).

Maps ``(op, genome_kind, impl)`` to a callable. Ops:

* ``"generation"``: ``fn(rng, pop, fitness, pop_size, cfg, genome) ->
  new_pop`` — one full GA generation (the signature of
  ``ga.next_generation``).
* ``"generation_eval"``: ``fn(rng, pop, fitness, pop_size, cfg, genome,
  fused) -> (new_pop, raw_fitness)`` — the same generation with the
  problem's fitness fused into the kernel (``fused`` is the static
  ``Problem.fused`` spec dict). Kernel-family entries also accept a
  ``consts=`` kwarg carrying the problem's array constants (f15's
  shift/permutation/rotation stack); drivers always pass it.

Built-in impls (registered on import of :mod:`repro.kernels.ga`):
``jnp`` (the classic ``core.ga`` path), ``pallas`` (the fused VMEM
megakernel, interpret mode on the CPU; auto-routes to the tiled engine
beyond a VMEM estimate), ``pallas_tiled`` (the grid-tiled streaming
megakernel, forced), ``pallas_ref`` (the pure-jnp oracle of both — same
counter RNG, same math; bit-exact vs ``pallas``/``pallas_tiled`` in
interpret mode for binary genomes). Register custom impls with::

    @register_kernel("generation", "binary", "my_impl")
    def my_generation(rng, pop, fitness, pop_size, cfg, genome): ...

and select them with ``EAConfig(impl="my_impl")`` — every driver
(batched, fused-scan, SPMD, async) dispatches through this table.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

_KERNELS: Dict[Tuple[str, str, str], Callable] = {}


def register_kernel(op: str, genome_kind: str, impl: str):
    """Decorator: register ``fn`` as the ``op`` kernel for
    ``(genome_kind, impl)``. Re-registration overwrites (last wins), so
    tests and downstream packages can shadow built-ins."""
    def deco(fn: Callable) -> Callable:
        _KERNELS[(op, genome_kind, impl)] = fn
        return fn
    return deco


def has_kernel(op: str, genome_kind: str, impl: str) -> bool:
    return (op, genome_kind, impl) in _KERNELS


def get_kernel(op: str, genome_kind: str, impl: str) -> Callable:
    key = (op, genome_kind, impl)
    if key not in _KERNELS:
        have = sorted({i for (o, g, i) in _KERNELS if o == op
                       and g == genome_kind})
        raise KeyError(
            f"no {op!r} kernel for genome {genome_kind!r} impl {impl!r}; "
            f"registered impls: {have}")
    return _KERNELS[key]


def available_impls(op: str = "generation",
                    genome_kind: str = None) -> List[str]:
    """Sorted impl names registered for ``op`` (optionally one genome kind
    only — otherwise impls available for *every* registered kind of op)."""
    if genome_kind is not None:
        return sorted({i for (o, g, i) in _KERNELS
                       if o == op and g == genome_kind})
    kinds = {g for (o, g, _) in _KERNELS if o == op}
    return sorted(i for i in {i for (o, _, i) in _KERNELS if o == op}
                  if all(has_kernel(op, g, i) for g in kinds))


def registered_kernels() -> List[Tuple[str, str, str]]:
    return sorted(_KERNELS)
