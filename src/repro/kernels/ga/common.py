"""Shared math of the fused GA generation: one function set, three executors.

The complete tournament/roulette-selection -> crossover -> mutation
(-> optional fused fitness evaluation) pipeline as pure functions of
arrays + static parameters, written exclusively in Pallas-lowerable ops —
one-hot matmul gathers instead of dynamic row gathers, (blocked)
triangular-matmul prefix sums instead of ``cumsum``, >=2-D iota,
counter-based RNG from :mod:`repro.kernels.ga.prng` — so the *same code*
runs inside the single-tile Pallas megakernel body (:mod:`.generation`),
inside the grid-tiled streaming kernel (:mod:`.tiling`), and as the
plain-jnp oracle (:mod:`.ref`). Parity between the paths is therefore
structural: any divergence is a lowering bug, not an algorithm fork.

The pipeline is split at its natural tiling seam:

* :func:`selection_plan` — everything that needs the *whole* fitness
  vector but only O(max_pop) memory: elite indices, tournament/roulette
  parent draws, two-point cut positions and the crossover gate. One call
  per generation; its outputs are five (max_pop,) "plan" vectors aligned
  with output rows (rows [0, elite) carry the elite indices with
  crossover/mutation disabled).
* :func:`child_tile_math` — the per-element crossover + mutation math for
  any (rows x cols) tile of the output, given the gathered parent tiles
  and the plan rows. All randomness is drawn with *global* counter
  offsets (:mod:`.prng`), so a tile at origin (row0, col0) computes
  bit-identical genes to the same region of a whole-array call.

:func:`generation_math` composes the two at offset (0, 0) over the full
(max_pop, L) tile — the untiled megakernel and the oracle run exactly
this; the tiled kernel runs the same plan once and `child_tile_math` per
output tile.

Static parameters arrive via :class:`GenerationSpec` (derived from
``EAConfig`` + ``GenomeSpec`` by ``ops.py``) rather than the dataclasses
themselves, keeping this module importable without ``repro.core``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import ordered_sum
from . import prng

# python float, not a jnp scalar: a module-level jnp constant would be a
# captured tracer inside the pallas kernel body
NEG_INF = float("-inf")

# Draw-site stream salts — one per random decision in the pipeline. The
# kernel and the oracle must consume identical streams, so these are the
# protocol, not an implementation detail.
SALT_SELECT_A = 0xA1
SALT_SELECT_B = 0xB2
SALT_CROSSOVER = 0xC3
SALT_CROSSOVER_GATE = 0xD4
SALT_MUTATE = 0xE5
SALT_MUTATE_NOISE = 0xF6

# Full f32 precision for matmuls of two arbitrary f32 operands (F15
# rotations, roulette prefix sums): on the TPU the default precision of
# XLA and Mosaic rounds f32 operands to bf16. One-hot gathers use
# onehot_matmul instead; 0/1 block sums are exact at any precision.
HIGHEST = jax.lax.Precision.HIGHEST

# Population-axis block size for the O(n^2) selection reductions
# (tournament candidate-fitness gather, roulette prefix sum / inverse
# CDF). Blocking bounds peak memory at O(n * block) instead of O(n^2) so
# selection stays viable at beyond-VMEM population sizes; every blocked
# reduction below is exact (max / integer-count) or reproduces the
# single-block matmul bit-for-bit when n <= block, so small-population
# streams are unchanged.
SELECTION_BLOCK = 4096


@dataclasses.dataclass(frozen=True)
class GenerationSpec:
    """Static description of one generation step (hashable, jit-constant)."""

    kind: str                    # 'binary' | 'float'
    length: int
    elite: int
    selection: str               # 'tournament' | 'roulette'
    tournament_k: int
    crossover: str               # 'two_point' | 'uniform' | 'blend'
    crossover_rate: float
    mutation_rate: float
    mutation_sigma: float
    low: float = -5.0
    high: float = 5.0
    blend_alpha: float = 0.5
    fused_eval: Optional[Tuple[Tuple[str, Any], ...]] = None

    def __post_init__(self):
        if self.kind not in ("binary", "float"):
            raise ValueError(f"unknown genome kind {self.kind!r}")
        if self.selection not in ("tournament", "roulette"):
            raise ValueError(f"unknown selection {self.selection!r}")
        if self.crossover not in ("two_point", "uniform", "blend"):
            raise ValueError(f"unknown crossover {self.crossover!r}")
        if self.crossover == "blend" and self.kind != "float":
            raise ValueError("blend crossover requires float genome")

    @property
    def eval_spec(self) -> Optional[Dict[str, Any]]:
        return dict(self.fused_eval) if self.fused_eval is not None else None


def spec_needs_consts(spec: "GenerationSpec") -> bool:
    """True when the spec's fused eval reads array constants (f15's shift /
    permutation / rotation stack) — such evals take a ``consts`` pytree as
    extra kernel operands."""
    return (spec.fused_eval is not None
            and dict(spec.fused_eval)["eval"] == "f15")


class SelectionPlan(NamedTuple):
    """Per-output-row decisions of one generation, aligned to (max_pop,).

    Rows [0, elite) are the elite: ``idx_a`` holds the elite source index,
    ``gate`` is 0 (child = parent A verbatim) and cuts are 0. Rows
    [elite, max_pop) are children: ``idx_a``/``idx_b`` are the selected
    parents, ``cut1``/``cut2`` the two-point crossover cuts (0 for other
    crossover kinds) and ``gate`` the crossover-rate Bernoulli."""

    idx_a: jax.Array   # (n,) int32 parent-A row
    idx_b: jax.Array   # (n,) int32 parent-B row
    cut1: jax.Array    # (n,) int32
    cut2: jax.Array    # (n,) int32
    gate: jax.Array    # (n,) int32 (0/1)


def _lanes(n: int) -> jax.Array:
    """(n,) int32 lane indices (2-D iota then reshape — TPU-safe)."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).reshape(n)


def _bf16_parts(x: jax.Array):
    """Split f32 ``x`` into three f32 arrays whose values are exact in
    bf16 and sum to ``x`` exactly: each keeps the top 16 bits of what the
    previous parts left over. Bit masks, not casts, so no compiler may
    fold the rounding away."""
    def top16(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = top16(x)
    mid = top16(x - hi)
    return hi, mid, x - hi - mid


def onehot_matmul(a: jax.Array, b: jax.Array, split=None) -> jax.Array:
    """``a @ b`` in f32 where one operand is a 0/1 one-hot, so each output
    element picks at most one value of the other operand.

    0/1 genes are exact in one default-precision pass. Float values are
    not: the TPU's default precision rounds f32 operands to bf16, and
    HIGHEST also splits the one-hot, which overflows VMEM at island sizes.
    ``split`` names the float operand (``"a"`` or ``"b"``): it goes through
    as three bf16-exact parts, which add back to each picked value bit for
    bit."""
    dot = lambda x, y: jnp.dot(x, y,  # noqa: E731
                               preferred_element_type=jnp.float32)
    if split is None:
        return dot(a, b)
    if split == "a":
        hi, mid, lo = _bf16_parts(a)
        return dot(hi, b) + dot(mid, b) + dot(lo, b)
    hi, mid, lo = _bf16_parts(b)
    return dot(a, hi) + dot(a, mid) + dot(a, lo)


def _gather_rows(popf: jax.Array, idx: jax.Array, split) -> jax.Array:
    """Row gather as a one-hot matmul: (m,) indices from (n, L) -> (m, L).
    MXU-native on TPU; bit-exact (see :func:`onehot_matmul`)."""
    n = popf.shape[0]
    onehot = (idx[:, None] == _lanes(n)[None, :]).astype(jnp.float32)
    return onehot_matmul(onehot, popf, split)


def gather_split(spec: "GenerationSpec"):
    """The ``split`` of :func:`onehot_matmul` for a parent gather: float
    genes need it, 0/1 genes do not."""
    return "b" if spec.kind == "float" else None


def _first_argmax(x: jax.Array) -> jax.Array:
    """Argmax over the last axis that picks the first maximum on ties, as
    ``jnp.argmax`` does in XLA. Built from max/min/compare: Mosaic's own
    arg-max reduction does not promise which tied index it returns, and
    trap fitness ties often."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    top = jnp.max(x, axis=-1, keepdims=True)
    return jnp.min(jnp.where(x == top, idx, x.shape[-1]), axis=-1)


def _argmax_lane(v: jax.Array) -> jax.Array:
    """Scalar argmax of a (n,) vector via a (1, n) reduction (TPU-safe)."""
    return _first_argmax(v.reshape(1, -1))[0]


def _tournament(k0, k1, masked: jax.Array, maxval: jax.Array,
                n_children: int, k: int, salt: int,
                block: Optional[int] = None) -> jax.Array:
    """(n_children,) parent indices via size-k tournaments over valid lanes.

    The candidate-fitness gather runs blocked over the population axis
    (max over a partition == global max, so the blocking is exact)."""
    n = masked.shape[0]
    block = min(n, block or SELECTION_BLOCK)
    cand = prng.randint(k0, k1, (n_children, k), maxval, salt)
    # one 2-D (n_children, block) compare per candidate column: a 3-D
    # (n_children, k, block) compare pads k to a full sublane tile on TPU
    cols = []
    for j in range(k):
        cf = jnp.full((n_children,), NEG_INF, jnp.float32)
        for b0 in range(0, n, block):
            bs = min(block, n - b0)
            lanes_b = b0 + _lanes(bs)
            hit = cand[:, j:j + 1] == lanes_b[None, :]
            part = jnp.max(jnp.where(hit, masked[b0:b0 + bs][None, :],
                                     NEG_INF), axis=-1)
            cf = jnp.maximum(cf, part)
        cols.append(cf[:, None])
    cand_f = jnp.concatenate(cols, axis=1)
    win = _first_argmax(cand_f)
    ks = jax.lax.broadcasted_iota(jnp.int32, (n_children, k), 1)
    return jnp.sum(jnp.where(ks == win[:, None], cand, 0), axis=1)


def _roulette(k0, k1, masked: jax.Array, maxval: jax.Array,
              n_children: int, salt: int,
              block: Optional[int] = None) -> jax.Array:
    """Fitness-proportional selection by inverse CDF. Padded lanes carry
    weight exactly 0 (they sit past the valid prefix, so the final clamp
    keeps boundary draws inside [0, pop_size)).

    The inclusive prefix sum runs as per-block lower-triangular matmuls
    with a running carry, and the inverse-CDF search as blocked integer
    counts — O(n * block) memory; identical to the single matmul when
    n <= block."""
    n = masked.shape[0]
    block = min(n, block or SELECTION_BLOCK)
    valid = jnp.isfinite(masked)
    finite = jnp.where(valid, masked, 0.0)
    lo = jnp.min(jnp.where(valid, masked, jnp.inf))
    w = jnp.where(valid, finite - lo + 1e-6, 0.0)
    cums = []
    carry = jnp.float32(0.0)
    for b0 in range(0, n, block):
        bs = min(block, n - b0)
        ri = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
        tril = (ci <= ri).astype(jnp.float32)
        cb = jnp.dot(tril, w[b0:b0 + bs][:, None],
                     preferred_element_type=jnp.float32,
                     precision=HIGHEST)[:, 0] + carry
        cums.append(cb)
        carry = cb[bs - 1]
    cum = cums[0] if len(cums) == 1 else jnp.concatenate(cums)
    total = cum[n - 1]
    u = prng.uniform(k0, k1, (n_children, 1), salt)[:, 0] * total
    idx = jnp.zeros((n_children,), jnp.int32)
    for b0 in range(0, n, block):
        bs = min(block, n - b0)
        idx = idx + jnp.sum((cum[b0:b0 + bs][None, :]
                             <= u[:, None]).astype(jnp.int32), axis=1)
    return jnp.minimum(idx, jnp.asarray(maxval, jnp.int32) - 1)


def selection_plan(k0: jax.Array, k1: jax.Array, fitness: jax.Array,
                   pop_size: jax.Array, spec: GenerationSpec,
                   n: int) -> SelectionPlan:
    """All per-row randomness of one generation: the elite indices, parent
    selections and per-row crossover draws, aligned to output rows.

    This is the only stage that touches the whole fitness vector; it costs
    O(n * SELECTION_BLOCK) memory and produces five (n,) vectors, so it
    runs unchanged whether the genome matrix itself fits in one VMEM tile
    or is streamed through the tiled kernel."""
    lanes = _lanes(n)
    masked = jnp.where(lanes < pop_size, fitness, NEG_INF)
    maxval = jnp.maximum(pop_size, 1)
    n_children = n - spec.elite

    # --- elite: iterative masked argmax (spec.elite is static, unrolled)
    elite_idx = []
    tmp = masked
    for _ in range(spec.elite):
        idx = _argmax_lane(tmp)
        elite_idx.append(idx)
        tmp = jnp.where(lanes == idx, NEG_INF, tmp)

    # --- selection
    if spec.selection == "tournament":
        ia = _tournament(k0, k1, masked, maxval, n_children,
                         spec.tournament_k, SALT_SELECT_A)
        ib = _tournament(k0, k1, masked, maxval, n_children,
                         spec.tournament_k, SALT_SELECT_B)
    else:
        ia = _roulette(k0, k1, masked, maxval, n_children, SALT_SELECT_A)
        ib = _roulette(k0, k1, masked, maxval, n_children, SALT_SELECT_B)

    # --- per-row crossover draws
    if spec.crossover == "two_point":
        cuts = prng.randint(k0, k1, (n_children, 2), spec.length + 1,
                            SALT_CROSSOVER)
        c1 = jnp.min(cuts, axis=1)
        c2 = jnp.max(cuts, axis=1)
    else:
        c1 = jnp.zeros((n_children,), jnp.int32)
        c2 = c1
    gate = prng.bernoulli(k0, k1, (n_children, 1), spec.crossover_rate,
                          SALT_CROSSOVER_GATE)[:, 0].astype(jnp.int32)

    ez = jnp.zeros((spec.elite,), jnp.int32)
    e = (jnp.stack(elite_idx).astype(jnp.int32) if spec.elite
         else jnp.zeros((0,), jnp.int32))
    cat = lambda a, b: jnp.concatenate([a, b])  # noqa: E731
    return SelectionPlan(idx_a=cat(e, ia.astype(jnp.int32)),
                         idx_b=cat(e, ib.astype(jnp.int32)),
                         cut1=cat(ez, c1.astype(jnp.int32)),
                         cut2=cat(ez, c2.astype(jnp.int32)),
                         gate=cat(ez, gate))


def child_tile_math(k0: jax.Array, k1: jax.Array, pa: jax.Array,
                    pb: jax.Array, cut1: jax.Array, cut2: jax.Array,
                    gate: jax.Array, spec: GenerationSpec,
                    row0=0, col0=0) -> jax.Array:
    """Crossover + mutation of one (rows, cols) output tile.

    ``pa``/``pb`` are the gathered parent tiles (f32); ``cut1``/``cut2``/
    ``gate`` the matching plan rows as (rows, 1) columns, the layout the
    tiled kernel's row blocks arrive in. ``(row0, col0)`` is the tile
    origin in the global (max_pop, length) output — all per-element
    randomness is drawn with global counter offsets so any tiling produces
    bit-identical genes. Elite rows (global row < spec.elite) pass parent A through
    untouched. Returns the f32 tile (cast to the population dtype by the
    caller)."""
    R, C = pa.shape
    length = spec.length
    # child-row offset into the (n_children, length) draw streams: global
    # output row r maps to child row r - elite (negative for elite rows —
    # their draws wrap harmlessly and are masked off below)
    off = (row0 - spec.elite, col0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) + row0
    is_child = rows >= spec.elite

    # --- crossover
    if spec.crossover == "two_point":
        pos = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1) + col0
        inside = (pos >= cut1) & (pos < cut2)
        kids = jnp.where(inside, pb, pa)
    elif spec.crossover == "uniform":
        take = prng.bernoulli(k0, k1, (R, C), 0.5, SALT_CROSSOVER, off,
                              length)
        kids = jnp.where(take, pb, pa)
    else:  # blend (float only, checked in GenerationSpec)
        a = spec.blend_alpha
        u = (prng.uniform(k0, k1, (R, C), SALT_CROSSOVER, off, length)
             * (1.0 + 2.0 * a) - a)
        kids = pa + u * (pb - pa)
    kids = jnp.where(gate != 0, kids, pa)

    # --- mutation (children only; elite rows pass through)
    hits = prng.bernoulli(k0, k1, (R, C), spec.mutation_rate, SALT_MUTATE,
                          off, length) & is_child
    if spec.kind == "binary":
        kids = jnp.where(hits, 1.0 - kids, kids)
    else:
        noise = (prng.normal(k0, k1, (R, C), SALT_MUTATE_NOISE, off, length)
                 * spec.mutation_sigma)
        kids = jnp.where(hits, kids + noise, kids)
        kids = jnp.where(is_child, jnp.clip(kids, spec.low, spec.high), kids)
    return kids


def rastrigin_terms(rot: jax.Array) -> jax.Array:
    """Element-wise Rastrigin terms z^2 - 10 cos(2 pi z) + 10 — shared by
    the fused in-kernel F15 tail, the streaming F15 eval kernel and the
    jnp references."""
    return (rot * rot
            - 10.0 * jnp.cos(jnp.float32(2.0 * jnp.pi) * rot) + 10.0)


def block_sums(x: jax.Array, width: int) -> jax.Array:
    """(n, C) -> (n, C // width) sums over consecutive ``width``-column
    blocks, as a matmul with a 0/1 block-membership matrix. Mosaic refuses
    the (n, C) -> (n, C // width, width) reshape; on 0/1 genes the matmul
    sums small integers, which is exact at any MXU precision."""
    C = x.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C // width), 0)
    start = jax.lax.broadcasted_iota(jnp.int32, (C, C // width), 1) * width
    member = ((col >= start) & (col < start + width)).astype(jnp.float32)
    return jnp.dot(x, member, preferred_element_type=jnp.float32)


def fused_fitness(popf: jax.Array, spec: Dict[str, Any],
                  consts: Optional[Dict[str, Any]] = None) -> jax.Array:
    """In-VMEM fitness of the freshly built population — the optional fused
    tail of the megakernel. ``popf`` is (n, L) float32; returns (n,) f32
    with the same maximization orientation as ``Problem.evaluate``.

    ``consts`` carries array constants for evals that need them (F15's
    shift vector, permutation and rotation stack); scalar-only evals
    ignore it. All kinds except ``f15`` are separable column sums, which
    is what lets the tiled kernel accumulate them per genome tile."""
    kind = spec["eval"]
    n = popf.shape[0]
    if kind == "trap":
        a, b, z, l = (float(spec["a"]), float(spec["b"]), float(spec["z"]),
                      int(spec["l"]))
        u = block_sums(popf, l)
        f = jnp.where(u <= z, a * (z - u) / z, b * (u - z) / (l - z))
        return ordered_sum(f)
    if kind == "royal_road":
        r = int(spec["r"])
        u = block_sums(popf, r)
        return jnp.float32(r) * (u >= r - 0.5).astype(jnp.float32).sum(-1)
    if kind == "onemax":
        return popf.sum(axis=-1)
    if kind == "rastrigin":
        return -rastrigin_terms(popf).sum(axis=-1)
    if kind == "sphere":
        return -(popf * popf).sum(axis=-1)
    if kind == "f15":
        # CEC2010-F15: shift, permute (one-hot matmul — MXU-native, exact),
        # rotate per group (static loop over the rotation stack), Rastrigin
        # per group. Viable in one VMEM tile only for small D; the tiled
        # engine streams the rotation stack through .tiling.f15_eval
        # instead of calling this inside the kernel.
        if consts is None:
            raise ValueError("fused f15 evaluation needs problem consts "
                             "(o, perm, M)")
        m = int(spec["m"])
        n_groups = int(spec["n_groups"])
        o, perm, M = consts["o"], consts["perm"], consts["M"]
        L = popf.shape[1]
        z = popf - o.astype(jnp.float32)
        # z[:, perm] as a one-hot matmul: P[r, c] = (perm[c] == r)
        ponehot = (jnp.asarray(perm, jnp.int32)[None, :]
                   == _lanes(L)[:, None]).astype(jnp.float32)
        zp = onehot_matmul(z, ponehot, split="a")
        total = jnp.zeros((n,), jnp.float32)
        for g in range(n_groups):
            rot = jnp.dot(zp[:, g * m:(g + 1) * m],
                          M[g].astype(jnp.float32),
                          preferred_element_type=jnp.float32,
                          precision=HIGHEST)
            total = total + rastrigin_terms(rot).sum(axis=-1)
        return -total
    raise ValueError(f"unknown fused eval {kind!r}")


def separable_fused_tile(kids: jax.Array, spec: Dict[str, Any],
                         col0, length: int) -> jax.Array:
    """Partial fused fitness of one genome tile for the separable evals
    (everything except f15): the (rows,) contribution of columns
    [col0, col0 + C) to the genome-wide reduction, accumulated across
    genome tiles by the tiled kernel.

    Padded genes (global column >= ``length``) are zeroed first; zero genes
    contribute exactly 0 to every eval except trap, whose all-zero blocks
    score ``a`` — the tiled wrapper aligns the tile width to the block size
    so padding forms whole blocks, and their a-contribution is subtracted
    here. ``col0`` may be traced (it comes from ``pl.program_id``)."""
    R, C = kids.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1) + col0
    kids = jnp.where(pos < length, kids, 0.0)
    part = fused_fitness(kids, spec)
    if spec["eval"] == "trap":
        a, l = float(spec["a"]), int(spec["l"])
        assert C % l == 0, (C, l)
        pad = jnp.maximum(jnp.asarray(col0, jnp.int32) + C - length, 0)
        part = part - jnp.float32(a) * (pad // l).astype(jnp.float32)
    return part


def generation_math(k0: jax.Array, k1: jax.Array, pop: jax.Array,
                    fitness: jax.Array, pop_size: jax.Array,
                    spec: GenerationSpec,
                    consts: Optional[Dict[str, Any]] = None):
    """One full GA generation on a VMEM-resident (max_pop, L) tile.

    Layout contract matches ``ga.next_generation``: slots [0, elite) hold
    the elite of the *valid* lanes, the rest hold fresh children; lanes
    >= pop_size are computed but algorithmically inert (they are never
    selected as parents and their fitness reads -inf).

    Returns the new (max_pop, L) population in ``pop.dtype`` — plus the
    (max_pop,) raw fused fitness when ``spec.fused_eval`` is set.
    ``consts`` is only read by fused evals with array constants (f15).
    """
    n, L = pop.shape
    assert L == spec.length, (L, spec.length)
    plan = selection_plan(k0, k1, fitness, pop_size, spec, n)
    popf = pop.astype(jnp.float32)
    pa = _gather_rows(popf, plan.idx_a, gather_split(spec))
    pb = _gather_rows(popf, plan.idx_b, gather_split(spec))
    kids = child_tile_math(k0, k1, pa, pb, plan.cut1[:, None],
                           plan.cut2[:, None], plan.gate[:, None], spec, 0, 0)
    new_pop = kids.astype(pop.dtype)
    if spec.fused_eval is not None:
        return new_pop, fused_fitness(kids, spec.eval_spec, consts)
    return new_pop
