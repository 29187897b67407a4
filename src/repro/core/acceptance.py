"""Pluggable immigrant-acceptance engine: who enters a pool, and where.

NodIO's server accepts every PUT and serves a uniformly random GET — the
paper itself notes this drives the pool toward premature convergence as
volunteers flood it with near-identical elites. Follow-up work on
asynchronous pool-based GAs shows the acceptance/replacement policy is the
lever that keeps diversity under volunteer churn, so this module makes it a
first-class registered strategy, mirroring the topology registry
(:mod:`repro.core.migration`): the fourth orthogonal axis of the engine
(topology x driver x runtime x **acceptance**).

An acceptance policy is a pure jittable function with the
:class:`AcceptancePolicy` signature::

    (pool_genomes, pool_fitness, cand_genomes, cand_fitness, cand_valid,
     rng, *, ptr, count, acc) -> (slots, new_ptr, new_count)

``slots`` is ``(k,)`` int32: candidate ``j`` overwrites resident
``slots[j]`` when ``slots[j] < capacity``; ``slots[j] == capacity``
rejects it. Slots must be **distinct** across accepted candidates (the
scatter is order-independent and therefore replica-deterministic under
SPMD) and every decision must be a deterministic function of the inputs —
under ``shard_map`` the candidates and the valid mask arrive
``all_gather``'d, so identical inputs on every shard must produce the
identical pool replica update.

Built-in policies
-----------------
``always``    the legacy ring insert — bit-for-bit the pre-engine
              ``pool_put_batch`` (the correctness anchor).
``elitist``   replace-worst-if-better: the r-th best candidate challenges
              the r-th worst resident (empty slots count as ``-inf``
              residents, so a cold pool fills first).
``crowding``  each candidate replaces its *nearest* resident by genome
              distance (deterministic lowest-index tie-break) iff fitter;
              when several candidates crowd the same resident only the
              fittest (then lowest-index) wins. Empty slots fill first,
              ring-style.
``dedup``     candidates within ``epsilon`` of any resident are rejected
              outright (the near-identical-elite flood), survivors fall
              through to ``elitist``.

Register your own with::

    @register_policy("my_policy")
    def my_policy(pool_g, pool_f, cand_g, cand_f, valid, rng, *,
                  ptr, count, acc):
        ...
        return slots, new_ptr, new_count

and select it via ``AcceptanceConfig(policy="my_policy")`` on
``MigrationConfig.acceptance``.

Two dispatch surfaces
---------------------
* :func:`apply_policy` — batch insert into a device :class:`PoolState`
  (called by ``pool.pool_put_batch``; every driver context routes through
  it: batched, fused-scan, SPMD, async).
* :func:`gate_immigrants` — the per-island receive gate: each destination
  island runs the same registered policy against the one-slot pool of its
  own current best, so permute/broadcast topologies (which bypass the
  shared pool) still dispatch through the acceptance engine. Rejected
  immigrants read ``-inf`` — the lost-XHR no-op every driver already
  honours. ``always`` accepts everything (the gate is skipped entirely,
  preserving the bit-for-bit anchor).

:func:`host_accept` is the numpy mirror used by the host
:class:`~repro.core.async_pool.PoolServer` so device and host pools make
the same replacement decisions for the same single-candidate stream.
"""
from __future__ import annotations

from typing import Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import AcceptanceConfig, Array, PoolState

NEG_INF = float("-inf")  # not a jnp scalar: that would start a backend


# ---------------------------------------------------------------------------
# Protocol + registry (mirrors migration.register_topology)
# ---------------------------------------------------------------------------
class AcceptancePolicy(Protocol):
    """One batch acceptance decision: candidates -> pool slots.

    Must be pure/jittable/vmappable, return distinct slots for accepted
    candidates (``capacity`` = reject) and be deterministic in its inputs
    (SPMD replica consistency). ``rng`` is provided for stochastic custom
    policies; the built-ins ignore it (a stochastic policy forfeits the
    async runtime's absorb-gate idempotence — document it if you register
    one).
    """

    def __call__(self, pool_genomes: Array, pool_fitness: Array,
                 cand_genomes: Array, cand_fitness: Array, cand_valid: Array,
                 rng: Array, *, ptr: Array, count: Array,
                 acc: AcceptanceConfig) -> Tuple[Array, Array, Array]: ...


ACCEPTANCE_POLICIES: Dict[str, AcceptancePolicy] = {}


def register_policy(name: str):
    """Decorator: register an :class:`AcceptancePolicy` under ``name``."""
    def deco(fn: AcceptancePolicy) -> AcceptancePolicy:
        ACCEPTANCE_POLICIES[name] = fn
        fn.policy_name = name
        return fn
    return deco


def available_policies() -> Tuple[str, ...]:
    return tuple(sorted(ACCEPTANCE_POLICIES))


def get_policy(name: str) -> AcceptancePolicy:
    try:
        return ACCEPTANCE_POLICIES[name]
    except KeyError:
        raise KeyError(f"unknown acceptance policy {name!r}; "
                       f"registered: {available_policies()}") from None


# ---------------------------------------------------------------------------
# Distance metric
# ---------------------------------------------------------------------------
def _distances(residents: Array, cands: Array, acc: AcceptanceConfig) -> Array:
    """(k, cap) candidate->resident genome distances under ``acc.metric``."""
    metric = acc.metric
    if metric == "auto":
        metric = "l2" if jnp.issubdtype(residents.dtype, jnp.floating) \
            else "hamming"
    if metric == "hamming":
        return (cands[:, None, :] != residents[None, :, :]).sum(-1) \
            .astype(jnp.float32)
    d = cands.astype(jnp.float32)[:, None, :] \
        - residents.astype(jnp.float32)[None, :, :]
    return jnp.sqrt((d * d).sum(-1))


def _count_after(pool_fitness: Array, slots: Array, count: Array) -> Array:
    """count + number of accepted candidates landing on empty (-inf) slots,
    saturated at capacity."""
    cap = pool_fitness.shape[0]
    accepted = slots < cap
    tgt_f = pool_fitness[jnp.clip(slots, 0, cap - 1)]
    filled = (accepted & ~jnp.isfinite(tgt_f)).sum().astype(jnp.int32)
    return jnp.minimum(count + filled, cap)


# ---------------------------------------------------------------------------
# Built-in policies
# ---------------------------------------------------------------------------
@register_policy("always")
def always_policy(pool_genomes: Array, pool_fitness: Array,
                  cand_genomes: Array, cand_fitness: Array, cand_valid: Array,
                  rng: Array, *, ptr: Array, count: Array,
                  acc: AcceptanceConfig) -> Tuple[Array, Array, Array]:
    """Legacy ring insert: the r-th valid candidate (stable original order)
    takes slot ``(ptr + r) % cap``; the pointer advances by the number of
    valid candidates. Bit-for-bit the pre-engine ``pool_put_batch``."""
    cap = pool_fitness.shape[0]
    rank = jnp.cumsum(cand_valid.astype(jnp.int32)) - 1
    slots = jnp.where(cand_valid, (ptr + rank) % cap, cap).astype(jnp.int32)
    n_valid = cand_valid.sum().astype(jnp.int32)
    return slots, (ptr + n_valid) % cap, jnp.minimum(count + n_valid, cap)


def _elitist_slots(pool_fitness: Array, cand_fitness: Array,
                   cand_valid: Array) -> Array:
    """Rank-paired replace-worst-if-better with distinct slots: the r-th
    best valid candidate challenges the r-th worst resident (stable
    index tie-breaks on both sides); empty (-inf) residents lose to any
    valid candidate, so cold pools fill front-first."""
    k = cand_fitness.shape[0]
    cap = pool_fitness.shape[0]
    res_order = jnp.argsort(pool_fitness, stable=True)       # worst first
    score = jnp.where(cand_valid, cand_fitness, NEG_INF)
    cand_order = jnp.argsort(-score, stable=True)            # best first
    target = res_order[jnp.minimum(jnp.arange(k), cap - 1)]
    accept = score[cand_order] > pool_fitness[target]
    slot_sorted = jnp.where(accept, target, cap).astype(jnp.int32)
    return jnp.zeros((k,), jnp.int32).at[cand_order].set(slot_sorted)


@register_policy("elitist")
def elitist_policy(pool_genomes: Array, pool_fitness: Array,
                   cand_genomes: Array, cand_fitness: Array, cand_valid: Array,
                   rng: Array, *, ptr: Array, count: Array,
                   acc: AcceptanceConfig) -> Tuple[Array, Array, Array]:
    slots = _elitist_slots(pool_fitness, cand_fitness, cand_valid)
    return slots, ptr, _count_after(pool_fitness, slots, count)


@register_policy("crowding")
def crowding_policy(pool_genomes: Array, pool_fitness: Array,
                    cand_genomes: Array, cand_fitness: Array,
                    cand_valid: Array, rng: Array, *, ptr: Array,
                    count: Array, acc: AcceptanceConfig,
                    ) -> Tuple[Array, Array, Array]:
    """Nearest-resident replacement: a candidate challenges the resident
    with the smallest genome distance (ties -> lowest slot) and wins iff
    fitter; candidates crowding the same resident are resolved to the
    fittest (ties -> lowest candidate index). Empty slots fill ring-style
    first so a cold pool behaves like ``always``."""
    k = cand_fitness.shape[0]
    cap = pool_fitness.shape[0]
    filled = jnp.isfinite(pool_fitness)
    n_empty = cap - filled.sum().astype(jnp.int32)
    empty_order = jnp.argsort(filled, stable=True)           # empty first
    vrank = jnp.cumsum(cand_valid.astype(jnp.int32)) - 1
    is_fill = cand_valid & (vrank < n_empty)
    fill_slot = empty_order[jnp.clip(vrank, 0, cap - 1)]

    dist = jnp.where(filled[None, :], _distances(pool_genomes, cand_genomes,
                                                 acc), jnp.inf)
    nearest = jnp.argmin(dist, axis=1)                       # ties -> low slot
    want = cand_valid & ~is_fill & (cand_fitness > pool_fitness[nearest])
    score = jnp.where(want, cand_fitness, NEG_INF)
    best_per_slot = jnp.full((cap,), NEG_INF).at[nearest].max(score)
    is_best = want & (score >= best_per_slot[nearest])
    idx = jnp.arange(k)
    win_idx = jnp.full((cap,), k).at[nearest].min(
        jnp.where(is_best, idx, k))
    win = is_best & (win_idx[nearest] == idx)

    slots = jnp.where(is_fill, fill_slot,
                      jnp.where(win, nearest, cap)).astype(jnp.int32)
    n_fill = is_fill.sum().astype(jnp.int32)
    return slots, (ptr + n_fill) % cap, jnp.minimum(count + n_fill, cap)


@register_policy("dedup")
def dedup_policy(pool_genomes: Array, pool_fitness: Array,
                 cand_genomes: Array, cand_fitness: Array, cand_valid: Array,
                 rng: Array, *, ptr: Array, count: Array,
                 acc: AcceptanceConfig) -> Tuple[Array, Array, Array]:
    """Reject candidates within ``acc.epsilon`` of any resident (the
    near-identical-elite flood the paper worries about) — or of an earlier
    surviving candidate in the same batch, matching the host mirror's
    one-PUT-at-a-time stream — then elitist. The batch therefore never
    inserts two epsilon-close entries at once (an earlier clone shadows
    later ones even if elitist ends up rejecting it — deliberately
    conservative)."""
    k = cand_fitness.shape[0]
    filled = jnp.isfinite(pool_fitness)
    dist = jnp.where(filled[None, :],
                     _distances(pool_genomes, cand_genomes, acc), jnp.inf)
    res_dup = (dist <= acc.epsilon).any(axis=1)
    pair = _distances(cand_genomes, cand_genomes, acc)      # (k, k)
    idx = jnp.arange(k)

    def scan_one(j, kept):
        earlier = (idx < j) & kept
        dup_j = res_dup[j] | (earlier & (pair[j] <= acc.epsilon)).any()
        return kept.at[j].set(cand_valid[j] & ~dup_j)

    kept = jax.lax.fori_loop(0, k, scan_one, jnp.zeros((k,), bool))
    slots = _elitist_slots(pool_fitness, cand_fitness, kept)
    return slots, ptr, _count_after(pool_fitness, slots, count)


# ---------------------------------------------------------------------------
# Dispatch surface 1: batch insert into a device PoolState
# ---------------------------------------------------------------------------
def apply_policy(pool: PoolState, genomes: Array, fitness: Array,
                 valid: Optional[Array], rng: Optional[Array],
                 acc: AcceptanceConfig) -> PoolState:
    """Insert up to ``k`` candidates through the registered policy.

    Keeps the legacy pre-selection: with more candidates than capacity the
    best ``cap`` valid entries survive (deterministic, replica-consistent)
    before the policy assigns slots.
    """
    k = genomes.shape[0]
    cap = pool.genomes.shape[0]
    if valid is None:
        valid = jnp.ones((k,), bool)
    if k > cap:
        score = jnp.where(valid, fitness, NEG_INF)
        _, top = jax.lax.top_k(score, cap)
        genomes, fitness, valid = genomes[top], fitness[top], valid[top]
        k = cap
    if rng is None:
        rng = jax.random.key(0)
    policy = get_policy(acc.policy)
    slots, new_ptr, new_count = policy(
        pool.genomes, pool.fitness, genomes, fitness, valid, rng,
        ptr=pool.ptr, count=pool.count, acc=acc)
    safe = jnp.where(slots < cap, slots, cap)    # cap = drop (out of range)
    return PoolState(
        genomes=pool.genomes.at[safe].set(
            genomes.astype(pool.genomes.dtype), mode="drop"),
        fitness=pool.fitness.at[safe].set(fitness, mode="drop"),
        ptr=jnp.asarray(new_ptr, jnp.int32),
        count=jnp.asarray(new_count, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Dispatch surface 2: per-island receive gate (every topology's deliveries)
# ---------------------------------------------------------------------------
def gate_immigrants(dest_genome: Array, dest_fitness: Array, imm_genome: Array,
                    imm_fitness: Array, rng: Array,
                    acc: AcceptanceConfig) -> Array:
    """Run the registered policy per destination island against the
    one-slot pool of its own current best; rejected deliveries read
    ``-inf`` (the lost-XHR no-op). On a one-slot pool ``elitist`` and
    ``crowding`` coincide (accept iff fitter than the resident best) and
    ``dedup`` additionally rejects epsilon-clones of it. Deterministic and
    collective-free, hence SPMD replica-safe. Callers skip this entirely
    for ``policy='always'`` (bit-for-bit anchor)."""
    policy = get_policy(acc.policy)
    n = imm_fitness.shape[0]
    keys = jax.random.split(rng, n)

    def one(dg, df, ig, if_, key):
        slots, _, _ = policy(
            dg[None], df[None], ig[None], if_[None],
            jnp.isfinite(if_)[None], key,
            ptr=jnp.int32(0), count=jnp.isfinite(df).astype(jnp.int32),
            acc=acc)
        return jnp.where(slots[0] < 1, if_, NEG_INF)

    return jax.vmap(one)(dest_genome, dest_fitness, imm_genome, imm_fitness,
                         keys)


# ---------------------------------------------------------------------------
# Numpy mirror for the host PoolServer (single-candidate stream)
# ---------------------------------------------------------------------------
def _host_distances(res_genomes: np.ndarray, cand: np.ndarray,
                    acc: AcceptanceConfig) -> np.ndarray:
    metric = acc.metric
    if metric == "auto":
        metric = "l2" if np.issubdtype(res_genomes.dtype, np.floating) \
            else "hamming"
    if metric == "hamming":
        return (res_genomes != cand[None, :]).sum(-1).astype(np.float64)
    d = res_genomes.astype(np.float64) - cand[None, :].astype(np.float64)
    return np.sqrt((d * d).sum(-1))


APPEND = "append"

#: Policies with an exact numpy host mirror in :func:`host_accept`. A
#: PoolServer can only be built with one of these; custom device-side
#: registrations are device-only until a mirror is added here.
HOST_MIRRORED = ("always", "crowding", "dedup", "elitist")


def host_accept(res_genomes: Optional[np.ndarray], res_fitness: np.ndarray,
                cand_genome: np.ndarray, cand_fitness: float,
                acc: AcceptanceConfig, capacity: int):
    """The host PoolServer's decision for one PUT, mirroring the device
    policies on a single-candidate stream so device and host pools agree:

    returns :data:`APPEND` (take a free slot — the device fill-first
    phase), an ``int`` victim index to overwrite, or ``None`` to reject.
    ``res_fitness`` carries the current residents (may be empty);
    ``res_genomes`` is only consulted by the distance policies
    ('crowding'/'dedup') and may be None for the others."""
    n = len(res_fitness)
    if acc.policy == "always":
        return APPEND                      # ring eviction handled by caller
    if acc.policy == "dedup" and n:
        if _host_distances(res_genomes, cand_genome, acc).min() \
                <= acc.epsilon:
            return None
    if n < capacity:
        return APPEND
    if acc.policy == "crowding":
        victim = int(_host_distances(res_genomes, cand_genome, acc).argmin())
    elif acc.policy in ("elitist", "dedup"):
        victim = int(np.asarray(res_fitness).argmin())
    else:
        raise KeyError(f"acceptance policy {acc.policy!r} has no host "
                       f"mirror; registered device policies: "
                       f"{available_policies()}")
    if cand_fitness > float(res_fitness[victim]):
        return victim
    return None
