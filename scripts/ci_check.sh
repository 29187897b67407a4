#!/usr/bin/env bash
# CI gate: tier-1 tests + a fast benchmark smoke.
#
#   scripts/ci_check.sh            # from anywhere inside the repo
#
# KNOWN_FAILING lists modules with pre-existing breakage excluded from the
# gate. Currently EMPTY: the gate is strict on the whole suite, on the one
# pinned JAX (requirements-ci.txt: jax/jaxlib 0.9.0). Add entries only with
# a tracking note in ROADMAP.md.
#
# The benchmark smoke runs the pool + migration + speed sections only
# (fig3 replays paper-scale evolution and roofline's dry-run section needs
# dry-run artifacts; fig4 runs in --smoke trim below) and leaves two
# machine-readable records behind:
#   BENCH_migration.json — epochs/sec per registered topology via the
#     fused driver, the bench_async sync-vs-async-under-churn section,
#     and the bench_acceptance policy x topology sweep;
#   BENCH_speed.json — the paper-style speed baseline (evals/sec +
#     time-to-solution per problem x genome length x generation-engine
#     impl, jnp vs pallas vs pallas_tiled) + the generation-roofline
#     section, two scenarios in smoke trim.
# Both carry "host" + "host.env" blocks (jax version/backend/device,
# XLA_FLAGS, interpret mode, autotune cache) so numbers are attributable.
# A third committed artifact, BENCH_server.json (networked pool service
# load harness), is gated by scripts/check_server_regress.py from a
# 500-volunteer smoke; its 10k-volunteer headline row is refreshed only
# by explicit `python benchmarks/server_load.py --full` runs.
# BENCH_speed.json is a *committed* artifact: the fresh smoke is written
# to a temp file and gated against the committed baseline (>30% evals/sec
# regression on the same backend fails) before replacing it locally.
# The GA kernel smokes below prove the fused generation megakernel —
# single-tile AND grid-tiled (>=2x2x2 grid) — bit-exact against the jnp
# oracle in interpret mode before any benchmark touches it.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"

KNOWN_FAILING=()

echo "== repro-lint (AST invariant analyzer: RNG/lock/purity/registry/donation) =="
# selfcheck first: a silently broken analyzer must not green-light the tree
python -m repro.analysis --selfcheck
python -m repro.analysis --format github --baseline analysis_baseline.json \
    src/ benchmarks/ examples/

echo "== tier-1 tests =="
python -m pytest -x -q ${KNOWN_FAILING[@]+"${KNOWN_FAILING[@]/#/--ignore=}"}

echo "== GA generation-kernel interpret smoke (pallas vs jnp oracle) =="
python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.core import EAConfig, make_rastrigin, make_trap
from repro.kernels import ga as gk

for problem, cx in ((make_trap(n_traps=8, l=4), "two_point"),
                    (make_rastrigin(dim=16), "blend")):
    cfg = EAConfig(max_pop=32, min_pop=16, crossover=cx)
    pop = problem.init_population(jax.random.key(0), 32)
    fit = problem.evaluate(problem.consts, pop)
    args = (jax.random.key(1), pop, fit, jnp.int32(24), cfg, problem.genome)
    got = gk.generation(*args, interpret=True)
    want = gk.generation_ref(*args)
    if problem.genome.kind == "binary":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
    gp, gf = gk.generation_eval(*args, problem.fused, interpret=True)
    np.testing.assert_allclose(np.asarray(gf),
                               np.asarray(problem.evaluate(problem.consts,
                                                           gp)),
                               rtol=1e-5, atol=1e-4)
    # tiled streaming engine forced through a >=2x2x2 grid: bit-identical
    # to the untiled kernel (binary: also to the oracle) for any tiling
    for tp, tl in ((16, 8), (8, 16)):
        tgot = gk.generation_tiled(*args, interpret=True,
                                   tile_pop=tp, tile_len=tl)
        np.testing.assert_array_equal(np.asarray(tgot), np.asarray(got))
        tgp, tgf = gk.generation_eval_tiled(*args, problem.fused,
                                            interpret=True, tile_pop=tp,
                                            tile_len=tl)
        np.testing.assert_array_equal(np.asarray(tgp), np.asarray(gp))
        np.testing.assert_allclose(np.asarray(tgf), np.asarray(gf),
                                   rtol=1e-5, atol=1e-4)
    print(f"  {problem.name}: generation + fused-eval + tiled-grid "
          "parity OK")
PY

echo "== kill -9 + resume smoke (segmented drivers + journaled PoolServer) =="
python scripts/kill_resume_smoke.py

echo "== observability smoke (traced volunteer_sim: trace parses, ledger balances) =="
# Async + churn exercises every counter; the timeline CLI exits 1 on an
# unbalanced ledger. obs_trace.json is uploaded as a CI artifact so a
# red run can be dropped straight into Perfetto (docs/observability.md).
python examples/volunteer_sim.py --runtime async --churn 0.4 --ticks 12 \
    --trace obs_trace.json --obs-json obs_counters.json
python - <<'PY'
import json
trace = json.load(open("obs_trace.json"))
events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
assert events, "traced run recorded no spans"
assert any(e["name"] == "driver.tick" for e in events), "no driver.tick spans"
obs = json.load(open("obs_counters.json"))
t = obs["totals"]
assert t["delivered"] == t["accepted"] + t["rejected"], f"ledger broken: {t}"
print(f"  obs smoke: {len(events)} spans, ledger "
      f"delivered={t['delivered']} accepted={t['accepted']} "
      f"rejected={t['rejected']} balanced OK")
PY
python -m repro.obs obs_trace.json --obs obs_counters.json

echo "== server load smoke (500 volunteers over the wire) + regression gate =="
# BENCH_server.json is a *committed* artifact whose headline row (10k
# volunteers) only a deliberate `benchmarks/server_load.py --full` run can
# regenerate — so unlike the speed flow, the fresh smoke is gated and then
# DISCARDED, never promoted over the baseline.
FRESH_SERVER="$(mktemp /tmp/bench_server_fresh.XXXXXX.json)"
python benchmarks/server_load.py --scenario smoke --json "$FRESH_SERVER"
if [[ -f BENCH_server.json ]]; then
    python scripts/check_server_regress.py --baseline BENCH_server.json \
        --fresh "$FRESH_SERVER" --threshold 0.30
else
    echo "no committed BENCH_server.json — first run, gate skipped"
fi
rm -f "$FRESH_SERVER"

echo "== Fig 4 smoke (tiled generation engine end-to-end) =="
python -m benchmarks.fig4_f15 --smoke

echo "== benchmark smoke (pool + migration + async + acceptance + speed) =="
FRESH_SPEED="$(mktemp /tmp/bench_speed_fresh.XXXXXX.json)"
python -m benchmarks.run --skip fig3 fig4 roofline --speed-json "$FRESH_SPEED"

echo "== speed-regression gate (fresh smoke vs committed BENCH_speed.json) =="
if [[ -f BENCH_speed.json ]]; then
    python scripts/check_speed_regress.py --baseline BENCH_speed.json \
        --fresh "$FRESH_SPEED" --threshold 0.30
else
    echo "no committed BENCH_speed.json — first run, gate skipped"
fi
mv "$FRESH_SPEED" BENCH_speed.json

echo "ci_check: OK"
