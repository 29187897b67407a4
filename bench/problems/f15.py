"""CEC2010 F15 of Tang et al. (2009), the D/m-group shifted m-rotated
Rastrigin: the program's problem, the plain reference in float64
(``f15_numpy``), the same reference in bfloat16 (the control of
``correct``) and a random population.

z = x - o, coordinates permuted by P and cut into D/m groups, each group
rotated by its own m x m orthogonal matrix, Rastrigin per group, summed
(minimised; the program reports it negated). The shift, permutation and
rotations are one fixed function, as CEC fixes them for all runs: drawn
here from the configuration's ``consts_seed``, never from the program.
Only :func:`build` imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np


def f15_consts(seed: int, dim: int, group: int) -> Dict[str, np.ndarray]:
    """The shift o ~ U[-5, 5]^D, permutation P and D/m rotations (QR of a
    Gaussian), drawn from ``seed`` in the precision the program runs
    (float32 values, returned as float64 so the reference computes on the
    very numbers the program gets)."""
    if dim % group:
        raise ValueError("dim must be divisible by the group size")
    rng = np.random.default_rng([seed, 15])
    o = rng.uniform(-5.0, 5.0, dim).astype(np.float32)
    perm = rng.permutation(dim).astype(np.int32)
    q = np.linalg.qr(rng.standard_normal((dim // group, group, group)))[0]
    return {"o": o.astype(np.float64), "perm": perm,
            "M": q.astype(np.float32).astype(np.float64)}


def build(p: Dict[str, Any]):
    """The program's problem holding the configuration's constants, and
    those constants in float64 for the reference."""
    import jax.numpy as jnp

    from repro.core import make_f15

    c64 = f15_consts(p["consts_seed"], p["dim"], p["group"])
    problem = make_f15(dim=p["dim"], group=p["group"])
    dev = {"o": jnp.asarray(c64["o"], jnp.float32),
           "perm": jnp.asarray(c64["perm"], jnp.int32),
           "M": jnp.asarray(c64["M"], jnp.float32)}
    return dataclasses.replace(problem, consts=dev), c64


def f15_numpy(consts: Dict[str, np.ndarray], pop: np.ndarray) -> np.ndarray:
    """(n, D) -> (n,) F15 value (minimised)."""
    o, perm, M = consts["o"], consts["perm"], consts["M"]
    G, m, _ = M.shape
    z = (pop - o)[:, perm].reshape(pop.shape[0], G, m)
    # each group's rotation, batched over groups: (G, n, m) @ (G, m, m)
    rot = np.matmul(z.transpose(1, 0, 2), M)
    r = rot * rot - 10.0 * np.cos(2 * np.pi * rot) + 10.0
    return r.sum(axis=(0, 2))


def reference(p: Dict[str, Any], consts, pop: np.ndarray) -> np.ndarray:
    return -f15_numpy(consts, pop.astype(np.float64))


def reference_bf16(p: Dict[str, Any], consts, pop: np.ndarray) -> np.ndarray:
    """The reference with every operation rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    o = jnp.asarray(consts["o"], bf)
    M = jnp.asarray(consts["M"], bf)
    G, m, _ = M.shape
    z = (jnp.asarray(pop, bf) - o)[:, jnp.asarray(consts["perm"])]
    z = z.reshape(pop.shape[0], G, m)
    rot = jnp.einsum("ngm,gmk->ngk", z, M, preferred_element_type=bf)
    r = (rot * rot - bf(10.0) * jnp.cos(bf(2 * np.pi) * rot)
         + bf(10.0)).astype(bf)
    acc = r[:, 0].sum(-1, dtype=bf)
    for g in range(1, G):
        acc = (acc + r[:, g].sum(-1, dtype=bf)).astype(bf)
    return -np.asarray(jax.device_get(acc), np.float64)


def random_population(p: Dict[str, Any], n: int, seed: int) -> np.ndarray:
    """Genes uniform in the problem's bounds: a population before any
    generation."""
    rng = np.random.default_rng([seed, 7])
    return rng.uniform(p["low"], p["high"],
                       (n, p["dim"])).astype(np.float32)
