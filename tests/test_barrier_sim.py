"""Barrier simulator: paper-claim validation + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import barrier, barrier_sim, fiveg, workloads
from repro.core.topology import DEFAULT, TeraPoolConfig

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Schedule structure.
# ---------------------------------------------------------------------------

def test_schedule_structure():
    s = barrier.kary_tree(32)
    assert s.n_levels == 2
    assert [l.group_size for l in s.levels] == [32, 32]
    s = barrier.kary_tree(8)   # log8(1024) not integer -> first level 2
    assert [l.group_size for l in s.levels] == [2, 8, 8, 8]
    assert np.prod([l.group_size for l in s.levels]) == 1024
    c = barrier.central_counter()
    assert c.n_levels == 1 and c.levels[0].group_size == 1024


@given(st.sampled_from([2, 4, 8, 16, 32, 64, 128, 256, 512, 1024]),
       st.sampled_from([64, 128, 256, 512, 1024]))
def test_schedule_products_cover_all_pes(radix, n_pes):
    if radix > n_pes:
        return
    s = barrier.kary_tree(radix, n_pes=n_pes)
    assert np.prod([l.group_size for l in s.levels]) == n_pes
    # spans increase monotonically and latencies are non-decreasing
    spans = [l.span for l in s.levels]
    assert spans == sorted(spans)
    lats = [l.latency for l in s.levels]
    assert lats == sorted(lats)


@pytest.mark.parametrize("n", [16, 33, 100, 1000, 1024, 2048])
def test_pe_mean_fixed_order(n):
    """pe_mean spells out the order of XLA's CPU mean (so CPU results
    keep their bits) and gives the same bits eagerly and inside a
    vmapped jit, where a plain jnp.mean may be reordered."""
    rng = np.random.default_rng(n)
    x = (rng.random((6, n)) * 3000 + rng.random((6, n)) * 1e-3
         ).astype(np.float32)
    want = np.asarray(jnp.mean(jnp.asarray(x), axis=-1))
    eager = np.asarray(barrier_sim.pe_mean(jnp.asarray(x)))
    fused = np.asarray(jax.jit(jax.vmap(
        lambda r: barrier_sim.pe_mean(r * 1.0)))(x))
    np.testing.assert_array_equal(eager, want)
    np.testing.assert_array_equal(fused, want)


def test_invalid_radix_rejected():
    with pytest.raises(ValueError):
        barrier.kary_tree(3)
    with pytest.raises(ValueError):
        barrier.kary_tree(2048)


# ---------------------------------------------------------------------------
# Simulator invariants (property-based).
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 16), st.sampled_from([2, 16, 128, 1024]),
       st.floats(0, 4096))
def test_exit_after_every_arrival(seed, radix, max_delay):
    arr = jax.random.uniform(jax.random.PRNGKey(seed), (1024,),
                             minval=0.0, maxval=max(max_delay, 1e-3))
    res = barrier_sim.simulate(arr, barrier.kary_tree(radix))
    assert float(res.exit_time) > float(res.last_arrival)
    assert float(res.mean_residency) > 0


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 16), st.sampled_from([4, 64, 1024]))
def test_shift_equivariance(seed, radix):
    """Shifting all arrivals by T shifts the exit by exactly T."""
    arr = jax.random.uniform(jax.random.PRNGKey(seed), (1024,),
                             minval=0.0, maxval=500.0)
    s = barrier.kary_tree(radix)
    r0 = barrier_sim.simulate(arr, s)
    r1 = barrier_sim.simulate(arr + 1000.0, s)
    np.testing.assert_allclose(float(r1.exit_time),
                               float(r0.exit_time) + 1000.0, rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_monotone_in_arrivals(seed):
    """Delaying one PE can never make the barrier finish earlier."""
    key = jax.random.PRNGKey(seed)
    arr = jax.random.uniform(key, (1024,), minval=0.0, maxval=300.0)
    s = barrier.kary_tree(16)
    base = float(barrier_sim.simulate(arr, s).exit_time)
    arr2 = arr.at[7].add(500.0)
    later = float(barrier_sim.simulate(arr2, s).exit_time)
    assert later >= base - 1e-4


def test_serialization_lower_bound():
    """Zero-delay central counter must serialize >= N_PE bank cycles."""
    res = barrier_sim.simulate(jnp.zeros(1024), barrier.central_counter())
    assert float(res.span_cycles) >= 1024


# ---------------------------------------------------------------------------
# Paper claims (EXPERIMENTS.md §Repro C1-C3).
# ---------------------------------------------------------------------------

def _span(radix, delay):
    s = barrier.kary_tree(radix)
    return float(barrier_sim.mean_span_cycles(KEY, s, delay, n_trials=8))


def test_c1_scoop_at_zero_delay():
    spans = {k: _span(k, 0.0) for k in (2, 16, 32, 512, 1024)}
    # central counter is the worst, mid radices the best
    assert spans[1024] == max(spans.values())
    assert min(spans, key=spans.get) in (16, 32)
    assert spans[2] > spans[16]          # log tree pays its level count


def test_c2_staircase_at_large_delay():
    spans = {k: _span(k, 2048.0) for k in (2, 16, 256, 1024)}
    # arrivals scattered -> central counter becomes the best
    assert spans[1024] == min(spans.values())
    assert spans[2] == max(spans.values())


def test_c3_sfr_for_10pct_overhead():
    """<10% overhead requires SFR between ~2k and ~10k cycles depending
    on arrival scatter (paper Fig. 4b)."""
    for delay, lo, hi in [(256.0, 500, 4000), (2048.0, 4000, 16000)]:
        best = None
        for radix in (16, 32, 64, 1024):
            s = barrier.kary_tree(radix)
            arr = barrier_sim.uniform_arrivals(KEY, delay, 1024, 8)
            res = barrier_sim.simulate(arr, s)
            cost = float(jnp.mean(res.mean_residency))
            best = cost if best is None else min(best, cost)
        sfr_needed = best * 9.0          # overhead <10% -> SFR >= 9x cost
        assert lo < sfr_needed < hi, (delay, sfr_needed)


# ---------------------------------------------------------------------------
# Kernel workloads (C5 qualitative ordering) + 5G app (C4).
# ---------------------------------------------------------------------------

def test_kernel_cdf_shapes():
    suite = workloads.benchmark_suite()
    gaps = {}
    for kernel, dims in suite.items():
        label, fn = max(dims.items())
        arr = fn(KEY)
        gaps[kernel] = float(workloads.cdf_first_last_gap(arr))
    # local-access kernels finish together; reduction scatters dotp
    assert gaps["axpy"] < gaps["dotp"]
    assert gaps["dotp"] > 900             # serialized atomic reduction
    assert gaps["conv2d"] > gaps["axpy"]  # border imbalance


def test_c4_5g_application():
    app = fiveg.FiveGConfig(n_rx=16, ffts_per_round=1)
    res = fiveg.compare_barriers(KEY, app, radix=32)
    speedup = float(res["speedup_partial"])
    assert 1.4 <= speedup <= 1.8, speedup          # paper: 1.6x

    app4 = fiveg.FiveGConfig(n_rx=64, ffts_per_round=4)
    res4 = fiveg.compare_barriers(KEY, app4, radix=32)
    frac = float(res4["partial"].sync_fraction)
    assert frac <= 0.062 + 0.01, frac              # paper: 6.2%
    assert float(res4["speedup_partial"]) > 1.0
    # speed-up shrinks as more FFTs amortize each barrier (paper)
    assert float(res4["speedup_partial"]) < speedup
    # parallel efficiency vs serial Snitch
    assert float(res4["partial"].speedup_serial) > 500
