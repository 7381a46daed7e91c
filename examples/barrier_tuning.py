"""Barrier tuning — the paper's key methodology as a library call, now
over the FULL mixed-radix schedule space.

Two layers of the tuner API:

1. `tuning.tune_barrier` sweeps EVERY composition of log2(N) into
   power-of-two level sizes (512 schedules at N=1024) x arrival scatter
   x trial through one compiled program, and `tuning.best_per_delay`
   reads off the winning composition against the best uniform radix —
   the generalized Fig. 4a tuning step.
2. `tuning.sweep_workloads` replays every kernel's MEASURED arrival
   batch (`workloads.arrival_batch`) under the whole schedule stack in
   one compiled call — the per-kernel tuning of Fig. 6 conditioned on
   the real arrival shapes, and `tuning.best_per_kernel` reads off the
   winner per kernel against the best uniform radix.
3. `tuning.tune_barrier(placements=...)` crosses the composition space
   with the counter-placement strategies of `repro.core.placement`:
   WHERE each counter lives (which L1 bank) becomes a tuned knob, and
   co-located counters pay real same-bank serialization.

    PYTHONPATH=src python examples/barrier_tuning.py
"""
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import placement, sweep, tuning

KEY = jax.random.PRNGKey(0)
DELAYS = (0.0, 128.0, 512.0, 2048.0)


def tune_random_delay():
    """The generalized Fig. 4a step: best composition per scatter.

    Runs on the telescoping simulator core (the default; pass
    ``core="scan"`` for the full-width oracle core, or ``trial_chunk=``
    to bound grid memory — both are bit-for-bit identical)."""
    res = tuning.tune_barrier(KEY, delays=DELAYS, n_trials=4)
    print(f"swept {len(res.schedules)} compositions x {len(DELAYS)} "
          f"delays in one compile")
    print("winners: " + ", ".join(
        f"d={int(d)}:{name}" for d, name in
        zip(res.delays.tolist(), sweep.best_schedule_per_delay(res))))
    print(f"{'delay':>6s} {'tuned schedule':>16s} {'span':>8s} "
          f"{'best uniform':>14s} {'span':>8s} {'gain':>6s}")
    for p in tuning.best_per_delay(res):
        print(f"{p.delay:6.0f} {p.schedule.name:>16s} "
              f"{p.mean_span:8.1f} {p.uniform_schedule.name:>14s} "
              f"{p.uniform_span:8.1f} {p.uniform_span / p.mean_span:5.2f}x")
    front = tuning.pareto_schedules(res)
    print(f"\nPareto front across delays ({len(front)} schedules): "
          + ", ".join(s.name for s in front))


def tune_kernels():
    """Per-kernel schedule selection on MEASURED arrivals (Fig. 6c,
    workload-conditioned edition): every kernel's arrival batch x every
    composition through one compiled call."""
    res = tuning.sweep_workloads(KEY, n_trials=4)
    central = res.names.index("1024")
    spans = res.mean_span                              # (S, K)
    print(f"\nswept {len(res.schedules)} compositions x "
          f"{len(res.kernels)} kernels x 4 trials in one compile")
    print(f"{'kernel':22s} {'tuned schedule':>16s} "
          f"{'vs uniform':>10s} {'vs central':>10s}")
    for j, p in enumerate(tuning.best_per_kernel(res)):
        c = float(spans[central, j])
        print(f"{p.kernel:22s} {p.schedule.name:>16s} "
              f"{p.uniform_span / p.mean_span:9.3f}x "
              f"{c / p.mean_span:9.2f}x")


def tune_placement():
    """Counter placement as the second design axis: the contention /
    latency trade-off behind the paper's leaf-local policy."""
    res = tuning.tune_barrier(KEY, delays=DELAYS, n_trials=4,
                              prune="hierarchy",
                              placements=placement.STRATEGIES)
    spans = jnp.mean(res.span_cycles, axis=-1)
    print(f"\nswept {len(res.schedules)} (composition, placement) points "
          f"x {len(DELAYS)} delays in one compile")
    print(f"{'delay':>6s} " + " ".join(f"{s:>18s}"
                                       for s in placement.STRATEGIES))
    for j, d in enumerate(res.delays.tolist()):
        cells = []
        for strat in placement.STRATEGIES:
            idx = [i for i, p in enumerate(res.placements)
                   if p.strategy == strat]
            best = float(jnp.min(spans[jnp.asarray(idx), j]))
            cells.append(f"{best:18.1f}")
        print(f"{d:6.0f} " + " ".join(cells))
    print("(mean span, best composition per strategy: co-locating "
          "counters on hub/central banks pays same-bank serialization; "
          "interleaving pays cluster-class hops)")


def main():
    tune_random_delay()
    tune_kernels()
    tune_placement()
    print("\nThe uniform-radix spread reproduces Fig. 6c (1.1-1.7x from "
          "radix selection); the tuned compositions squeeze the "
          "remaining few percent the paper attributes to hierarchy-"
          "matched trees, and the placement sweep shows the paper's "
          "leaf-local counter allocation is the dominant corner of the "
          "contention-vs-latency trade-off.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
