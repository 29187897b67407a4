"""Ackley's trap function as NodIO's Fig. 3 runs it: the program's
problem, the plain reference in float64, the same reference in bfloat16
(the control of ``correct``) and a random population.

Per block of l bits with u ones: a (z - u) / z if u <= z, else
b (u - z) / (l - z); the fitness is the sum over blocks (maximised).
Only :func:`build` imports the program.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def build(p: Dict[str, Any]):
    """The program's problem, and the constants the reference computes
    with (none for the trap)."""
    from repro.core import make_trap

    return make_trap(n_traps=p["n_traps"], l=p["l"], a=p["a"], b=p["b"],
                     z=p["z"]), None


def trap_numpy(pop: np.ndarray, a: float, b: float, z: float,
               l: int) -> np.ndarray:
    """(n, n_traps * l) of 0/1 genes -> (n,) trap fitness."""
    n = pop.shape[0]
    u = pop.reshape(n, -1, l).astype(np.float64).sum(-1)
    f = np.where(u <= z, a * (z - u) / z, b * (u - z) / (l - z))
    return f.sum(-1)


def reference(p: Dict[str, Any], consts, pop: np.ndarray) -> np.ndarray:
    return trap_numpy(pop, p["a"], p["b"], p["z"], p["l"])


def reference_bf16(p: Dict[str, Any], consts, pop: np.ndarray) -> np.ndarray:
    """The reference with every operation rounded to bfloat16."""
    import jax.numpy as jnp

    bf = jnp.bfloat16
    a, b, z, l = p["a"], p["b"], p["z"], p["l"]
    n = pop.shape[0]
    u = jnp.asarray(pop).reshape(n, -1, l).astype(bf).sum(-1, dtype=bf)
    f = jnp.where(u <= z, bf(a) * (bf(z) - u) / bf(z),
                  bf(b) * (u - bf(z)) / bf(l - z)).astype(bf)
    acc = f[:, 0]
    for j in range(1, f.shape[1]):
        acc = (acc + f[:, j]).astype(bf)
    return np.asarray(acc, np.float64)


def random_population(p: Dict[str, Any], n: int, seed: int) -> np.ndarray:
    """Fair coins: a population before any generation."""
    rng = np.random.default_rng([seed, 7])
    return rng.integers(0, 2, (n, p["n_traps"] * p["l"]), dtype=np.int8)
