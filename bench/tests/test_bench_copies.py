"""The benchmark's copies of the oracles and the traffic generators
equal the program's today, bit for bit, at N=64.  The copies are the
yardstick; this test shows they started out as the program's own."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import arrivals as gen
from bench.lib import reference
from bench.tests.helpers import small_cell
from repro.core import (barrier, barrier_sim, energy, placement, tuning,
                        workloads)
from repro.core.topology import TeraPoolConfig

N = 64
CFG = TeraPoolConfig(n_pes=N)
MACHINE = reference.machine_of(small_cell("terapool.tune")["config"])
ENERGY = reference.energy_of(small_cell("terapool.tune")["config"])
SIZES = [(2,) * 6, (64,), (8, 8), (4, 2, 8), (2, 32), (8, 2, 4)]


def _arrivals(seed, shape=(3, N), scale=700.0):
    return np.asarray(scale * jax.random.uniform(jax.random.PRNGKey(seed),
                                                 shape), np.float32)


def _same(got, want):
    for col in reference.COLUMNS:
        g, w = np.asarray(got[col]), np.asarray(getattr(want, col))
        assert g.shape == w.shape, col
        assert np.array_equal(g, w), (col, g, w)


def test_machine_matches_program_config():
    for f in dataclasses.fields(reference.Machine):
        assert getattr(MACHINE, f.name) == getattr(CFG, f.name)
    assert MACHINE.wakeup_cycles == CFG.wakeup_cycles
    assert dataclasses.asdict(ENERGY) == dataclasses.asdict(
        energy.DEFAULT_ENERGY)


def test_schedule_spaces_match():
    assert reference.compositions(N) == tuning.enumerate_compositions(N, CFG)
    assert (reference.hierarchy_compositions(MACHINE)
            == tuning.hierarchy_compositions(N, CFG))
    for r in barrier.all_radices(N, CFG):
        assert reference.kary_sizes(r, N) == barrier.kary_tree(r, cfg=CFG).sizes


@pytest.mark.parametrize("strategy", placement.STRATEGIES)
def test_placements_match(strategy):
    for sizes in SIZES:
        s = barrier.mixed_radix_tree(sizes, cfg=CFG)
        plc = placement.place_counters(s, strategy, CFG)
        b = reference.banks(sizes, strategy, MACHINE)
        assert tuple(tuple(r) for r in b) == plc.banks
        lat = reference.counter_latencies(sizes, b, MACHINE)
        assert tuple(tuple(r) for r in lat) == plc.latencies
        static, active = reference.count_events(sizes, lat, MACHINE, ENERGY)
        stat_p, act_p, _ = energy.schedule_energy_constants(s, plc, CFG)
        assert (static, active) == (stat_p, act_p)


@pytest.mark.parametrize("strategy", placement.STRATEGIES)
def test_simulate_placed_reference(strategy):
    arr = _arrivals(1)
    for sizes in SIZES:
        s = barrier.mixed_radix_tree(sizes, cfg=CFG)
        plc = placement.place_counters(s, strategy, CFG)
        _same(reference.simulate_placed(arr, sizes, strategy, MACHINE,
                                        ENERGY),
              placement.simulate_placed_reference(arr, s, plc, CFG))


@pytest.mark.parametrize("strategy", (None,) + placement.STRATEGIES)
def test_energy_reference(strategy):
    arr = _arrivals(2)
    for sizes in SIZES:
        s = barrier.mixed_radix_tree(sizes, cfg=CFG)
        plc = (None if strategy is None
               else placement.place_counters(s, strategy, CFG))
        got = reference.energy_reference(arr, sizes, MACHINE, ENERGY,
                                         strategy=strategy)
        want = energy.energy_reference(arr, s, CFG, placement=plc)
        assert np.array_equal(got, np.asarray(want))


def test_simulate_reference():
    arr = _arrivals(3)
    for sizes in SIZES:
        s = barrier.mixed_radix_tree(sizes, cfg=CFG)
        _same(reference.simulate_unplaced(arr, sizes, MACHINE, ENERGY),
              barrier_sim.simulate_reference(arr, s, CFG))


@pytest.mark.parametrize("strategy", (None, "group_hub"))
def test_simulate_robust_reference(strategy):
    base = jnp.asarray(_arrivals(4, (4, N)))
    arr = np.asarray(workloads.apply_faults(
        jax.random.PRNGKey(5), base,
        workloads.PEFaultModel(p_fail=0.05, p_stall=0.05, p_straggler=0.1)))
    assert np.isinf(arr).any()
    spec = barrier.fault_spec(timeout_cycles=300.0, quorum_frac=0.9)
    for sizes in SIZES:
        s = barrier.mixed_radix_tree(sizes, cfg=CFG)
        plc = (None if strategy is None
               else placement.place_counters(s, strategy, CFG))
        _same(reference.simulate_robust(arr, sizes, MACHINE, ENERGY,
                                        timeout_cycles=300.0,
                                        quorum_frac=0.9, strategy=strategy),
              barrier_sim.simulate_robust_reference(arr, s, CFG,
                                                    placement=plc,
                                                    faults=spec))


def test_fig6_kernel_names():
    assert gen.FIG6_KERNELS == workloads.FIG6_KERNELS


@pytest.mark.parametrize("kernel",
                         workloads.FIG6_KERNELS + ("straggler_lognormal",))
def test_arrival_batch(kernel):
    key = jax.random.PRNGKey(6)
    got = gen.arrival_batch(key, kernel, (5, N), MACHINE)
    want = workloads.arrival_batch(key, kernel, (5, N), CFG)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_apply_faults():
    key = jax.random.PRNGKey(7)
    arr = _arrivals(8, (2, 3, N))
    for p in ({"p_fail": 0.02, "p_stall": 0.03, "p_straggler": 0.05},
              {"p_straggler": 0.2}, {}):
        got = gen.apply_faults(key, arr, gen.PEFaultModel(**p))
        want = workloads.apply_faults(key, arr, workloads.PEFaultModel(**p))
        assert np.array_equal(np.asarray(got), np.asarray(want))
