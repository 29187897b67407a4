"""The comparison that decides ``correct`` fails when it should.

Runs the cells' whole path on the CPU at a small size (skipping the look
for a chip): a sound run comes out correct; the controls (the reference in
bfloat16 in the program's place, the program with its gather in bfloat16)
and each fault planted in the timed path underneath come out not
correct."""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from harness import check, spec
from harness import control as control_lib
from repro.kernels.ga import get_kernel, register_kernel

BENCH = spec.load_benchmark()


def _small(cell_name, impl):
    cell = spec.workload(BENCH, cell_name)
    cfg = copy.deepcopy(spec.config(BENCH, cell["config"]))
    if cfg["problem"]["kind"] == "trap":
        cfg["problem"]["n_traps"] = 8
        cfg["genome"]["length"] = 32
        cfg["population"], cfg["islands"] = 256, 2
        cfg["ea"]["generations_per_epoch"] = 50
        cfg["budget_evals"] = 256 * 50 * 4
    else:
        cfg["problem"].update(dim=100, group=10)
        cfg["genome"]["length"] = 100
        cfg["population"] = 256
        cfg["ea"]["generations_per_epoch"] = 10
        cfg["budget_evals"] = 256 * 10 * 3
    mix = dict(spec.traffic(cell["traffic"]), impl=impl)
    return cell, cfg, mix


def _run(cell_name, impl="pallas_ref", control=False, topology=None):
    cell, cfg, mix = _small(cell_name, impl)
    if topology:
        cfg["migration"]["topology"] = topology
    return bench_run.run_cell(
        BENCH, cell, cfg, mix, seed=2**33 + 7, seconds=0.3, trace=False,
        t_start=time.perf_counter(), devs_used=jax.devices()[:1],
        control=control)


def _reg(name, fn):
    for kind in ("binary", "float"):
        register_kernel("generation_eval", kind, name)(fn)


def _ref(*args, **kw):
    kind = args[5].kind
    return get_kernel("generation_eval", kind, "pallas_ref")(*args, **kw)


def _unchanged(rng, pop, fit, size, cfg, genome, fused, consts=None):
    return pop, fit


def _altered(*args, **kw):
    new_pop, new_fit = _ref(*args, **kw)
    row = new_pop[-1]
    bad = (1 - row[0]) if row.dtype == jnp.int8 else row[0] + 0.5
    return new_pop.at[-1, 0].set(bad.astype(new_pop.dtype)), new_fit


def _half(*args, **kw):
    pop, fit = args[1], args[2]
    new_pop, new_fit = _ref(*args, **kw)
    h = pop.shape[0] // 2
    return (jnp.concatenate([new_pop[:h], pop[h:]]),
            jnp.concatenate([new_fit[:h], fit[h:]]))


FAULTS = {"bench_fault_unchanged": _unchanged,
          "bench_fault_altered": _altered, "bench_fault_half": _half}


def _unregister(*names):
    from repro.kernels.ga import registry

    for key in list(registry._KERNELS):
        if key[2] in names:
            registry._KERNELS.pop(key)


@pytest.fixture
def fault_kernels():
    """The faults as kernel impls, registered for one test only: other
    tests in the same process check the registry's inventory."""
    for name, fn in FAULTS.items():
        _reg(name, fn)
    yield
    _unregister(*FAULTS)


@pytest.fixture
def bf16_gather():
    yield control_lib.plant_bf16_gather("pallas_ref")
    _unregister(control_lib.PLANT)


@pytest.mark.parametrize("cell", ["trap40.solve", "f15.budget"])
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("cell", ["trap40.solve", "f15.budget"])
def test_control_is_not_correct(cell):
    res = _run(cell, control=True)
    assert not res["correct"]
    assert res["check"]["fit_gap"]["value"] > res["check"]["fit_gap"]["limit"]


@pytest.mark.parametrize("cell", ["trap40.solve", "f15.budget"])
@pytest.mark.parametrize("fault,number", [
    ("bench_fault_unchanged", "stalled_blocks"),
    ("bench_fault_altered", "fit_gap"),
    ("bench_fault_half", "stalled_blocks")])
def test_fault_is_not_correct(fault_kernels, cell, fault, number):
    res = _run(cell, impl=fault)
    assert not res["correct"]
    assert res["check"][number]["value"] > res["check"][number]["limit"]


def test_bf16_gather_is_not_correct(bf16_gather):
    """The program with its parent gather in bfloat16 keeps its fitness
    true to its genes; only the genes' precision shows it."""
    sound = _run("f15.budget")
    bad = _run("f15.budget", impl=bf16_gather)
    assert not bad["correct"]
    assert bad["check"]["fit_gap"]["value"] <= bad["check"]["fit_gap"]["limit"]
    genes = bad["check"]["bf16_exact_genes"]
    assert genes["value"] > genes["limit"]
    assert sound["check"]["bf16_exact_genes"]["value"] < genes["limit"]


def test_bf16_exact_counts_genes_inside_the_bounds():
    g = np.array([0.5, 1.0 + 2.0**-20, -5.0, 5.0, 3.0, 1.1], np.float32)
    # 0.5 and 3.0 are bfloat16s; the bounds are left out; 1.1 is not one
    assert check.bf16_exact(g, -5.0, 5.0) == (2, 4)
    assert check.bf16_exact(g.astype(jnp.bfloat16).astype(np.float32),
                            -5.0, 5.0) == (4, 4)
