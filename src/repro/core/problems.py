"""Benchmark problems from the paper (+ standard extras).

The paper's two workloads:

* **Trap** (Ackley 1987): concatenation of ``n_traps`` deceptive blocks of
  ``l`` bits; parameters a (deceptive peak), b (global peak), z (slope break).
  Paper settings: 40-trap, l=4, a=1, b=2, z=3 — optimum = all-ones = 40*b.
* **CEC2010-F15**: D/m-group shifted and m-rotated Rastrigin (D=1000, m=50).
  z = x - o, groups are formed by a random permutation P, each group is
  rotated by an m×m orthogonal matrix and fed through Rastrigin. Minimized;
  exposed here as maximization of -F15.

Every problem is a :class:`Problem` with a ``consts`` pytree (shift vectors,
rotation matrices…) so that ``evaluate`` stays a pure jittable function of
``(consts, pop)``. ``evaluate`` dispatches to a Pallas kernel when
``impl='pallas'`` (TPU target; interpret-mode on CPU) and to the pure-jnp
reference otherwise — the reference IS the oracle the kernels are tested
against.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ordered_sum

# f32 matmuls at full f32 precision: the TPU's default rounds operands to bf16
HIGHEST = jax.lax.Precision.HIGHEST

from .types import Array, GenomeSpec


@dataclasses.dataclass(frozen=True)
class Problem:
    """A fitness-maximization problem.

    evaluate(consts, pop) -> (n,) float32 fitness for pop of shape (n, L).
    ``optimum`` (if known) enables success detection at fitness >= optimum-eps.
    ``fused`` (optional) is a static spec dict (python scalars only, e.g.
    ``{"eval": "trap", "a": 1.0, ...}``) advertising that this problem's
    fitness can be folded into a registered ``generation_eval`` megakernel
    (repro.kernels.ga) — under ``EAConfig(impl='pallas')`` the drivers then
    evolve *and* evaluate in one VMEM-resident kernel. Evals that also need
    array constants (F15's shift/permutation/rotation stack) keep those in
    ``consts``; the drivers pass ``consts`` alongside ``fused`` so the
    kernel can take them as operands (streamed per group by the tiled
    engine).
    """

    name: str
    genome: GenomeSpec
    evaluate: Callable[[Any, Array], Array] = dataclasses.field(compare=False)
    consts: Any = dataclasses.field(default=None, compare=False)
    optimum: Optional[float] = None
    fused: Optional[Dict[str, Any]] = dataclasses.field(default=None,
                                                        compare=False)

    def init_population(self, rng: Array, n: int) -> Array:
        g = self.genome
        if g.kind == "binary":
            return jax.random.bernoulli(rng, 0.5, (n, g.length)).astype(jnp.int8)
        return jax.random.uniform(rng, (n, g.length), jnp.float32, g.low, g.high)


# ---------------------------------------------------------------------------
# Trap
# ---------------------------------------------------------------------------
def trap_fitness_ref(consts: Dict[str, float], pop: Array) -> Array:
    """Pure-jnp trap fitness. pop: (n, n_traps*l) of {0,1} int8 -> (n,) f32.

    Per block with u = ones count:
        f(u) = a * (z - u) / z          if u <= z
             = b * (u - z) / (l - z)    otherwise
    """
    a, b, z, l = consts["a"], consts["b"], consts["z"], consts["l"]
    n = pop.shape[0]
    blocks = pop.reshape(n, -1, l).astype(jnp.float32)
    u = blocks.sum(-1)
    f = jnp.where(u <= z, a * (z - u) / z, b * (u - z) / (l - z))
    return ordered_sum(f)  # the fused kernels' order, on every backend


def make_trap(n_traps: int = 40, l: int = 4, a: float = 1.0, b: float = 2.0,
              z: float = 3.0, impl: str = "jnp") -> Problem:
    consts = {"a": float(a), "b": float(b), "z": float(z), "l": int(l)}
    if impl == "pallas":
        from repro.kernels.trap import ops as trap_ops

        evaluate = partial(trap_ops.trap_fitness, n_traps=n_traps)
    else:
        evaluate = trap_fitness_ref
    return Problem(
        name=f"trap{n_traps}x{l}",
        genome=GenomeSpec("binary", n_traps * l),
        evaluate=evaluate,
        consts=consts,
        optimum=n_traps * b,
        fused=dict(consts, eval="trap"),
    )


# ---------------------------------------------------------------------------
# OneMax (sanity workload)
# ---------------------------------------------------------------------------
def make_onemax(length: int = 128) -> Problem:
    def evaluate(consts, pop):
        return pop.astype(jnp.float32).sum(-1)

    return Problem(
        name=f"onemax{length}",
        genome=GenomeSpec("binary", length),
        evaluate=evaluate,
        consts=None,
        optimum=float(length),
        fused={"eval": "onemax"},
    )


# ---------------------------------------------------------------------------
# Royal Road (Mitchell/Forrest/Holland R1 — paper-family integer workload)
# ---------------------------------------------------------------------------
def royal_road_fitness_ref(consts: Dict[str, int], pop: Array) -> Array:
    """R1 royal road: the genome is ``n_blocks`` schemata of ``r`` bits;
    each fully-set block contributes ``r``. pop: (n, n_blocks*r) int8 ->
    (n,) f32. A plateau-heavy deceptive-free complement to the trap."""
    r = consts["r"]
    n = pop.shape[0]
    u = pop.reshape(n, -1, r).astype(jnp.float32).sum(-1)
    return jnp.float32(r) * (u >= r - 0.5).astype(jnp.float32).sum(-1)


def make_royal_road(n_blocks: int = 16, r: int = 8) -> Problem:
    consts = {"r": int(r)}
    return Problem(
        name=f"royalroad{n_blocks}x{r}",
        genome=GenomeSpec("binary", n_blocks * r),
        evaluate=royal_road_fitness_ref,
        consts=consts,
        optimum=float(n_blocks * r),
        fused={"eval": "royal_road", "r": int(r)},
    )


# ---------------------------------------------------------------------------
# Rastrigin family
# ---------------------------------------------------------------------------
def rastrigin(z: Array) -> Array:
    """Basic separable Rastrigin over the last axis (to be minimized)."""
    return jnp.sum(z * z - 10.0 * jnp.cos(2.0 * jnp.pi * z) + 10.0, axis=-1)


def make_rastrigin(dim: int = 20, bound: float = 5.12) -> Problem:
    def evaluate(consts, pop):
        return -rastrigin(pop)

    return Problem(
        name=f"rastrigin{dim}",
        genome=GenomeSpec("float", dim, -bound, bound),
        evaluate=evaluate,
        consts=None,
        optimum=0.0,
        fused={"eval": "rastrigin"},
    )


# ---------------------------------------------------------------------------
# CEC2010 F15: D/m-group shifted, m-rotated Rastrigin
# ---------------------------------------------------------------------------
def make_f15_consts(rng: Array, dim: int = 1000, group: int = 50,
                    shared_rotation: bool = False) -> Dict[str, Array]:
    """Build the benchmark constants: shift o, permutation P, rotations M.

    M matrices are orthogonal (QR of a gaussian). ``shared_rotation`` mimics
    the official suite's single m×m matrix reused for each group.
    """
    if dim % group:
        raise ValueError("dim must be divisible by group size")
    n_groups = dim // group
    k_o, k_p, k_m = jax.random.split(rng, 3)
    o = jax.random.uniform(k_o, (dim,), jnp.float32, -5.0, 5.0)
    perm = jax.random.permutation(k_p, dim)
    n_mats = 1 if shared_rotation else n_groups
    gs = jax.random.normal(k_m, (n_mats, group, group), jnp.float32)
    qs = jnp.linalg.qr(gs)[0]
    if shared_rotation:
        qs = jnp.broadcast_to(qs, (n_groups, group, group))
    return {"o": o, "perm": perm, "M": qs}


def f15_ref(consts: Dict[str, Array], pop: Array) -> Array:
    """Pure-jnp F15 (to be minimized): (n, D) -> (n,).

    z = x - o; groups z[P] reshaped (n, G, m); rotated per group via M_g;
    Rastrigin per group, summed.
    """
    o, perm, M = consts["o"], consts["perm"], consts["M"]
    n_groups, m, _ = M.shape
    z = (pop - o)[:, perm]
    zg = z.reshape(pop.shape[0], n_groups, m)
    rot = jnp.einsum("ngm,gmk->ngk", zg, M, precision=HIGHEST)
    return rastrigin(rot).sum(-1)


def make_f15(rng: Optional[Array] = None, dim: int = 1000, group: int = 50,
             impl: str = "jnp", shared_rotation: bool = False) -> Problem:
    if rng is None:
        rng = jax.random.key(2010)
    consts = make_f15_consts(rng, dim, group, shared_rotation)
    if impl == "pallas":
        from repro.kernels.rastrigin import ops as f15_ops

        def evaluate(consts, pop):
            return -f15_ops.f15(consts, pop)
    else:
        def evaluate(consts, pop):
            return -f15_ref(consts, pop)

    return Problem(
        name=f"f15_d{dim}m{group}",
        genome=GenomeSpec("float", dim, -5.0, 5.0),
        evaluate=evaluate,
        consts=consts,
        optimum=0.0,
        fused={"eval": "f15", "m": int(group), "n_groups": int(dim // group)},
    )


def make_sphere(dim: int = 30, bound: float = 5.12) -> Problem:
    def evaluate(consts, pop):
        return -jnp.sum(pop * pop, axis=-1)

    return Problem(
        name=f"sphere{dim}",
        genome=GenomeSpec("float", dim, -bound, bound),
        evaluate=evaluate,
        consts=None,
        optimum=0.0,
        fused={"eval": "sphere"},
    )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Callable[..., Problem]] = {
    "trap": make_trap,
    "onemax": make_onemax,
    "royal_road": make_royal_road,
    "rastrigin": make_rastrigin,
    "f15": make_f15,
    "sphere": make_sphere,
}


def make_problem(name: str, **kwargs) -> Problem:
    if name not in _REGISTRY:
        raise KeyError(f"unknown problem {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)
