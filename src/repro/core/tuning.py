"""Exhaustive, one-compile tuning over the mixed-radix schedule space.

The paper's headline 1.6x comes from *fine-tuning* the synchronization
tree to the machine hierarchy (Sec. 5): the best schedule for TeraPool
is often NOT a uniform radix but a composition matched to the 8/16/8
Tile/Group/Cluster structure.  This module opens that full design
space:

* :func:`enumerate_compositions` — every way to split ``log2(N)`` tree
  depth into power-of-two level sizes: ``2**(log2(N)-1)`` schedules
  (512 at N=1024), a strict superset of every uniform radix.
* :func:`hierarchy_compositions` — the hierarchy-aware pruned search:
  only compositions whose level spans land on Tile/Group/cluster
  boundaries, where counters never straddle a locality class
  (128 schedules at N=1024).
* :func:`multicluster_schedules` — the scale-out space for
  :class:`~repro.core.topology.MultiClusterConfig` machines: every
  intra-cluster composition jointly crossed with every inter-cluster
  tree (4096-16384 PEs through the same one-compile sweep).
* :func:`tune_barrier` — the exhaustive tuner: every composition x
  placement x delay x trial through the single compiled scanned core
  of :mod:`repro.core.sweep` — one compile for the whole design space.
  The ``placements`` axis crosses each composition with the named
  counter-placement strategies of :mod:`repro.core.placement`, making
  WHERE counters live a tuned knob next to the tree shape.
* :func:`best_per_delay` / :func:`pareto_schedules` — selection: the
  argmin (schedule, placement) at each delay, and the schedules not
  dominated at every delay simultaneously — optionally across BOTH the
  cycles and energy objectives (:mod:`repro.core.energy`).
* :func:`pareto_front` — the true 2-D latency x energy front at one
  delay: the non-dominated (schedule, placement) design points, sorted
  fastest-first, exposing the latency/energy budget trade-off.
* :func:`best_placed_schedule` — the jointly tuned (schedule,
  placement) pair for one arrival scatter (the 5G ``sync="placed"``
  mode consumes this).  Both selectors take ``objective=`` ("cycles" |
  "energy" | "edp") to pick the tuning metric.
* :func:`sweep_workloads` / :func:`best_per_kernel` /
  :func:`tune_for_workload` — WORKLOAD-conditioned tuning: the same
  one-compile grid driven by each kernel's *measured* arrival
  distribution (:mod:`repro.core.workloads`) instead of uniform
  scatters, so the winning schedule reflects e.g. ``dotp``'s
  atomic-reduction tail or ``conv2d``'s bimodal border imbalance.
  :func:`tune_for_arrivals` tunes against an explicit arrival matrix
  (the 5G ``sync="workload"`` per-epoch specialization consumes this),
  and :func:`tuned_for_workload` is the lru-cached schedule store
  keyed on (kernel, N, cfg).

Because the uniform radices (and the paper's leaf-local placement) are
a subset of the enumeration, the tuned best can only match or beat the
best uniform radix — the acceptance bar of tests/test_tuning.py and
tests/test_placement.py.
"""
from __future__ import annotations

import functools
import math
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import barrier, placement as placement_mod, sweep
from . import workloads as workloads_mod
from .barrier import BarrierSchedule
from .placement import CounterPlacement
from .topology import DEFAULT, TeraPoolConfig


def enumerate_compositions(n_pes: int | None = None,
                           cfg: TeraPoolConfig = DEFAULT
                           ) -> List[Tuple[int, ...]]:
    """All ordered factorizations of ``N`` into level sizes >= 2, leaf
    level first — for power-of-two ``N`` this is exactly the classic
    composition-of-``log2(N)`` space in the same lexicographic order
    (``2**(log2(N) - 1)`` entries), and for non-power-of-two ``N``
    (768-PE clusters, 12-way groups, cluster counts) it is its natural
    generalization.  Every :func:`~repro.core.barrier.kary_tree` shape
    (first level adapted, uniform tail) appears among them, as does the
    central counter ``(N,)``.
    """
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    if n < 2:
        raise ValueError(f"n_pes must be >= 2, got {n}")

    def facts(remaining: int):
        if remaining == 1:
            yield ()
            return
        for f in range(2, remaining + 1):
            if remaining % f:
                continue
            for rest in facts(remaining // f):
                yield (f,) + rest

    return list(facts(n))


def _hier_segments(n: int, cfg: TeraPoolConfig) -> List[int]:
    """Locality-class segment sizes of ``n`` PEs under ``cfg``, leaf
    first: Tile share, Group share, cluster share — topped by the
    cluster count when ``cfg`` is a :class:`~repro.core.topology.
    MultiClusterConfig` and ``n`` spans several clusters.  ``gcd``
    (not ``min``) aligns each segment for non-power-of-two shapes;
    both agree on power-of-two machines."""
    top: List[int] = []
    ppc = getattr(cfg, "pes_per_cluster", n)
    if getattr(cfg, "n_clusters", 1) > 1 and n > ppc and n % ppc == 0:
        top = [n // ppc]
        n = ppc
    t = math.gcd(n, cfg.pes_per_tile)
    g = math.gcd(n // t, cfg.tiles_per_group)
    c = n // (t * g)
    return [s for s in (t, g, c) if s > 1] + top


def hierarchy_compositions(n_pes: int | None = None,
                           cfg: TeraPoolConfig = DEFAULT
                           ) -> List[Tuple[int, ...]]:
    """The hierarchy-aware pruned search space: compositions whose
    cumulative spans include every Tile/Group — and, on a
    multi-cluster machine, cluster — boundary inside ``N``, so no
    level's counters straddle a locality class.  The product of the
    per-segment compositions — 4 x 8 x 4 = 128 schedules for the full
    8/16/8 cluster versus 512 exhaustive."""
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    out: List[Tuple[int, ...]] = []
    segs = _hier_segments(n, cfg)
    if not segs:
        return [(n,)] if n > 1 else []

    def seg_parts(size: int):
        return enumerate_compositions(size, cfg) if size > 1 else [()]

    def product(i: int):
        if i == len(segs):
            yield ()
            return
        for head in seg_parts(segs[i]):
            for rest in product(i + 1):
                yield head + rest

    for comp in product(0):
        out.append(comp)
    return out


def multicluster_compositions(cfg, *,
                              intra: Sequence[Tuple[int, ...]] | None = None,
                              inter: Sequence[Tuple[int, ...]] | None = None
                              ) -> List[Tuple[int, ...]]:
    """The hierarchical multi-cluster search space: every intra-cluster
    composition extended by every inter-cluster tree, leaf first.

    ``intra`` defaults to the hierarchy-pruned per-cluster space
    (:func:`hierarchy_compositions` over ``cfg.pes_per_cluster``) and
    ``inter`` to the full factorization space of ``cfg.n_clusters``
    (:func:`enumerate_compositions`), so the joint sweep tunes the
    inside-the-cluster tree and the cross-cluster reduction together —
    the scale-out analogue of the paper's Sec. 5 fine-tuning.
    """
    if intra is None:
        intra = hierarchy_compositions(cfg.pes_per_cluster, cfg)
    if inter is None:
        inter = (enumerate_compositions(cfg.n_clusters, cfg)
                 if cfg.n_clusters > 1 else [()])
    return [tuple(ic) + tuple(xc) for ic in intra for xc in inter]


def multicluster_schedules(cfg, *,
                           intra: Sequence[Tuple[int, ...]] | None = None,
                           inter: Sequence[Tuple[int, ...]] | None = None,
                           partial: bool = False) -> List[BarrierSchedule]:
    """Materialize :func:`multicluster_compositions` as schedules over
    the full ``cfg.n_pes`` machine (one stacked
    :class:`~repro.core.barrier.LevelTable` shape — the whole space is
    one compile through the sweep entry points).

    Energy folds in automatically: inter-cluster levels carry
    ``cfg.lat_remote`` as their latency, which
    :func:`repro.core.energy.schedule_energy_constants` prices per
    atomic hop — so a remote-cluster counter costs ~5x a Group-local
    one in pJ just as it does in cycles, and the 2-D
    :func:`pareto_front` over this space trades wide low-traffic
    inter-cluster trees against deep low-latency ones."""
    return [barrier.mixed_radix_tree(c, cfg=cfg, partial=partial)
            for c in multicluster_compositions(cfg, intra=intra,
                                               inter=inter)]


def all_schedules(n_pes: int | None = None,
                  cfg: TeraPoolConfig = DEFAULT, *,
                  prune: str = "none",
                  partial: bool = False) -> List[BarrierSchedule]:
    """Materialize the search space as schedules.  ``prune`` in
    {"none", "hierarchy"} selects exhaustive vs hierarchy-aligned."""
    if prune == "none":
        comps = enumerate_compositions(n_pes, cfg)
    elif prune == "hierarchy":
        comps = hierarchy_compositions(n_pes, cfg)
    else:
        raise ValueError(f"unknown prune mode {prune!r}")
    return [barrier.mixed_radix_tree(c, cfg=cfg, partial=partial)
            for c in comps]


def tune_barrier(key, n_pes: int | None = None,
                 delays: Sequence[float] = (0.0, 128.0, 512.0, 2048.0),
                 n_trials: int = 16, cfg: TeraPoolConfig = DEFAULT, *,
                 prune: str = "none",
                 schedules: Sequence[BarrierSchedule] | None = None,
                 placements: Sequence[str] | None = None,
                 core: str | None = None,
                 trial_chunk: int | None = None,
                 shard: bool = True,
                 faults=None) -> sweep.SweepResult:
    """Sweep the full mixed-radix design space in ONE compiled call.

    Every composition shares the padded level-table shape, so the whole
    composition x delay x trial grid reuses the single compiled scanned
    core (the same program the uniform-radix Fig. 4 sweep compiles).
    Pass ``schedules`` to tune over an explicit candidate list instead
    of the enumeration.

    ``placements`` — a sequence of strategy names from
    :data:`repro.core.placement.STRATEGIES` — adds the counter
    placement axis: the stack becomes the cross product composition x
    strategy (the result's ``schedules``/``placements`` tuples align
    entry-for-entry), still through the single compiled core.  ``None``
    keeps the placement-free legacy sweep.

    ``core`` / ``trial_chunk`` / ``shard`` / ``faults`` pass through to
    :func:`repro.core.sweep.sweep_schedules`: simulator-core selection,
    bounded-memory trial chunking (bit-for-bit identical),
    schedule-axis device sharding, and the timeout/quorum
    :class:`~repro.core.barrier.FaultSpec` switching the grid to the
    degradation-tolerant cores (pair it with the robustness
    objectives: ``"p99_cycles"``, ``"worst_cycles"``,
    ``"completion"``).
    """
    with TraceAnnotation("repro.tune.enumerate") as span:
        if schedules is None:
            schedules = all_schedules(n_pes, cfg, prune=prune)
        scheds, placs = _cross_placements(schedules, placements, cfg)
        span.set_metadata(n=scheds[0].n_pes if scheds else 0,
                          rows=len(scheds))
    return sweep.sweep_schedules(key, scheds, delays, n_trials, cfg,
                                 placements=placs, core=core,
                                 trial_chunk=trial_chunk, shard=shard,
                                 faults=faults)


def _cross_placements(schedules: Sequence[BarrierSchedule],
                      placements: Sequence[str] | None,
                      cfg: TeraPoolConfig
                      ) -> Tuple[Sequence[BarrierSchedule],
                                 Sequence[CounterPlacement] | None]:
    """Cross a schedule stack with named placement strategies into
    aligned (schedules, placements) stacks; ``None`` passes the
    placement-free stack through."""
    if placements is None:
        return tuple(schedules), None
    for strat in placements:
        if not isinstance(strat, str):
            raise TypeError(
                "placements must be strategy names; pass explicit "
                "CounterPlacements through sweep.sweep_schedules")
    scheds: List[BarrierSchedule] = []
    placs: List[CounterPlacement | None] = []
    for strat in placements:
        for s in schedules:
            if s.hw:
                continue   # the event unit has no counters to place
            scheds.append(s)
            placs.append(placement_mod.place_counters(s, strat, cfg))
    # Hardware event-unit schedules join the stack exactly once, with
    # no placement — the strategy axis is meaningless for them.
    for s in schedules:
        if s.hw:
            scheds.append(s)
            placs.append(None)
    return scheds, placs


class TunedPoint(NamedTuple):
    """The winning schedule (+ placement) at one arrival scatter."""

    delay: float
    schedule: BarrierSchedule
    mean_span: float              # its Fig. 4a metric
    uniform_schedule: BarrierSchedule   # best uniform radix at this delay
    uniform_span: float
    placement: object = None      # CounterPlacement | None of the winner


def _is_baseline(plc) -> bool:
    """Placements equivalent to the paper's model (span-heuristic
    fallback or explicit leaf-local) qualify as the uniform baseline."""
    return plc is None or plc.strategy == "leaf_local"


def _uniform_baseline(res) -> Tuple[tuple, List[int]]:
    """The per-point placements of a sweep result plus the indices of
    its baseline-placed uniform-radix schedules (the shared selection
    scaffolding of :func:`best_per_delay` / :func:`best_per_kernel`)."""
    placs = res.placements or (None,) * len(res.schedules)
    uniform = [i for i, s in enumerate(res.schedules)
               if s.radix and _is_baseline(placs[i])]
    if not uniform:
        raise ValueError(
            "schedule stack contains no baseline-placed uniform radix")
    return placs, uniform


def _column_winners(col: jnp.ndarray, uniform: List[int]) -> Tuple[int, int]:
    """(overall argmin, argmin among the uniform baseline) of one span
    column."""
    i = int(jnp.argmin(col))
    iu = uniform[int(jnp.argmin(col[jnp.asarray(uniform)]))]
    return i, iu


def best_per_delay(res: sweep.SweepResult) -> List[TunedPoint]:
    """The argmin-span (schedule, placement) at each delay, paired with
    the best UNIFORM radix under the paper's leaf-local placement at
    that delay (the Fig. 4a baseline)."""
    spans = jnp.mean(res.span_cycles, axis=-1)          # (S, D)
    placs, uniform = _uniform_baseline(res)
    out = []
    for j, delay in enumerate(res.delays.tolist()):
        col = spans[:, j]
        i, iu = _column_winners(col, uniform)
        out.append(TunedPoint(
            delay=float(delay), schedule=res.schedules[i],
            mean_span=float(col[i]),
            uniform_schedule=res.schedules[iu],
            uniform_span=float(col[iu]),
            placement=placs[i]))
    return out


_OBJECTIVE_GRIDS = ("cycles", "energy", "p99_cycles", "worst_cycles",
                    "completion")


def _objective_grid(res, objective: str) -> jnp.ndarray:
    """(S, D) selection metric per objective: mean Fig. 4a span
    (``"cycles"``), mean episode energy in pJ (``"energy"``), or their
    product, the energy-delay product (``"edp"``).

    The robustness objectives tune the TAIL instead of the mean —
    ``"p99_cycles"`` (99th-percentile span over trials; the ``"lower"``
    interpolation keeps it finite whenever <1% of trials hang),
    ``"worst_cycles"`` (max span over trials), and ``"completion"``
    (mean abandoned-PE count, minimized — the completion-rate-maximal
    pick under fault-injected sweeps; identically zero without
    faults)."""
    sp = jnp.mean(res.span_cycles, axis=-1)
    if objective == "cycles":
        return sp
    if objective == "p99_cycles":
        return jnp.percentile(res.span_cycles, 99.0, axis=-1,
                              method="lower")
    if objective == "worst_cycles":
        return jnp.max(res.span_cycles, axis=-1)
    if objective == "completion":
        return jnp.mean(res.abandoned_pes.astype(jnp.float32), axis=-1)
    en = jnp.mean(res.energy, axis=-1)
    if objective == "energy":
        return en
    if objective == "edp":
        return sp * en
    raise ValueError(
        f"unknown objective {objective!r}; choose from "
        f"('cycles', 'energy', 'edp', 'p99_cycles', 'worst_cycles', "
        f"'completion')")


def pareto_schedules(res: sweep.SweepResult,
                     objectives: Sequence[str] = ("cycles",)
                     ) -> List[BarrierSchedule]:
    """Schedules on the Pareto front across delays: no other schedule
    is at least as good in every (delay, objective) column and strictly
    better in one.

    ``objectives`` generalizes the front from best-by-cycles to the
    joint latency x energy trade: with ``("cycles", "energy")`` each
    schedule's point is its mean span AND mean energy at every delay,
    so a schedule survives if nothing beats it across the whole 2-D
    grid simultaneously.  The default reproduces the legacy
    cycles-only front."""
    cols = []
    for obj in objectives:
        if obj not in _OBJECTIVE_GRIDS:
            raise ValueError(
                f"unknown objective {obj!r}; choose from "
                f"{_OBJECTIVE_GRIDS}")
        cols.append(np.asarray(_objective_grid(res, obj)))
    sp = np.concatenate(cols, axis=1)     # (S, D * n_objectives)
    keep = []
    for i in range(sp.shape[0]):
        dominated = np.any(np.all(sp <= sp[i], axis=1)
                           & np.any(sp < sp[i], axis=1))
        if not dominated:
            keep.append(res.schedules[i])
    return keep


class ParetoPoint(NamedTuple):
    """One non-dominated (schedule, placement) design point of the 2-D
    latency x energy front at a single delay/kernel column."""

    schedule: BarrierSchedule
    placement: object             # CounterPlacement | None
    name: str                     # canonical label incl. @strategy
    mean_span: float              # cycles (Fig. 4a metric)
    mean_energy: float            # pJ per episode


def pareto_front(res, column: int = 0) -> List[ParetoPoint]:
    """The true 2-D latency x energy Pareto front at one delay column
    (:class:`~repro.core.sweep.SweepResult`) or kernel column
    (:class:`~repro.core.sweep.ArrivalSweepResult`): every (schedule,
    placement) point no other point beats on BOTH mean span and mean
    energy (with one strict).  Sorted fastest-first, so the first entry
    is the 1-D best-by-cycles winner and the last is the
    energy-minimal design — the curve the tuner exposes to a
    latency/energy budget trade-off."""
    sp = np.asarray(jnp.mean(res.span_cycles, axis=-1))[:, column]
    en = np.asarray(jnp.mean(res.energy, axis=-1))[:, column]
    placs = res.placements or (None,) * len(res.schedules)
    names = res.names
    front = []
    for i in range(sp.shape[0]):
        dominated = np.any((sp <= sp[i]) & (en <= en[i])
                           & ((sp < sp[i]) | (en < en[i])))
        if not dominated:
            front.append(ParetoPoint(
                schedule=res.schedules[i], placement=placs[i],
                name=names[i], mean_span=float(sp[i]),
                mean_energy=float(en[i])))
    return sorted(front, key=lambda p: (p.mean_span, p.mean_energy))


def knee_point(front: Sequence[ParetoPoint]) -> ParetoPoint:
    """The knee of a 2-D latency x energy front: the point closest (in
    min-max-normalized Euclidean distance) to the utopia corner
    ``(span_min, energy_min)``.  This is the balanced pick the 5G
    ``sync="pareto"`` mode and ``objective="pareto"`` serving requests
    use — faster than the energy-minimal end, cheaper than the
    best-by-cycles end, deterministic for a given front."""
    if not front:
        raise ValueError("empty Pareto front")
    if len(front) == 1:
        return front[0]
    sp = np.array([p.mean_span for p in front], np.float64)
    en = np.array([p.mean_energy for p in front], np.float64)
    ns = (sp - sp.min()) / ((sp.max() - sp.min()) or 1.0)
    ne = (en - en.min()) / ((en.max() - en.min()) or 1.0)
    return front[int(np.argmin(np.hypot(ns, ne)))]


class TunedColumn(NamedTuple):
    """Per-kernel-column winner of a batched arrival sweep under one
    request's objective — the unit the serving daemon hands back."""

    schedule: BarrierSchedule
    placement: object             # CounterPlacement | None
    name: str
    mean_span: float
    mean_energy: float


def best_for_arrival_stack(res, objectives) -> List[TunedColumn]:
    """Decompose one batched ``sweep_arrivals`` grid into per-kernel
    winners, each column selected under ITS OWN objective (``"cycles"``,
    ``"energy"``, ``"edp"``, or ``"pareto"`` = knee of the 2-D front).

    This is the batch-composition hook of
    :class:`repro.runtime.serving.TuningServer`: requests with different
    objectives share a single compile/dispatch and are split here.
    ``objectives`` is one string (applied to every column) or a sequence
    with one entry per kernel column."""
    n_cols = len(res.kernels)
    if isinstance(objectives, str):
        objectives = (objectives,) * n_cols
    if len(objectives) != n_cols:
        raise ValueError(
            f"{len(objectives)} objectives for {n_cols} kernel columns")
    sp = np.asarray(jnp.mean(res.span_cycles, axis=-1))
    en = np.asarray(jnp.mean(res.energy, axis=-1))
    placs = res.placements or (None,) * len(res.schedules)
    names = res.names
    out = []
    for j, obj in enumerate(objectives):
        if obj == "pareto":
            p = knee_point(pareto_front(res, column=j))
            out.append(TunedColumn(p.schedule, p.placement, p.name,
                                   p.mean_span, p.mean_energy))
            continue
        i = int(np.argmin(np.asarray(_objective_grid(res, obj))[:, j]))
        out.append(TunedColumn(res.schedules[i], placs[i], names[i],
                               float(sp[i, j]), float(en[i, j])))
    return out


def best_schedule(key, n_pes: int | None = None, delay: float = 0.0,
                  n_trials: int = 16, cfg: TeraPoolConfig = DEFAULT, *,
                  prune: str = "none", partial: bool = False,
                  core: str | None = None,
                  objective: str = "cycles") -> BarrierSchedule:
    """Convenience: the single tuned schedule for one arrival scatter
    (used by the 5G ``sync="tuned"`` modes).  ``objective`` selects the
    tuning metric: ``"cycles"`` (mean span — the legacy behavior),
    ``"energy"`` (mean episode energy) or ``"edp"`` (their product)."""
    schedules = all_schedules(n_pes, cfg, prune=prune, partial=partial)
    res = tune_barrier(key, n_pes, delays=(delay,), n_trials=n_trials,
                       cfg=cfg, schedules=schedules, core=core)
    i = int(jnp.argmin(_objective_grid(res, objective)[:, 0]))
    return schedules[i]


def best_placed_schedule(key, n_pes: int | None = None, delay: float = 0.0,
                         n_trials: int = 16,
                         cfg: TeraPoolConfig = DEFAULT, *,
                         prune: str = "none", partial: bool = False,
                         placements: Sequence[str] = placement_mod.STRATEGIES,
                         core: str | None = None,
                         objective: str = "cycles"
                         ) -> Tuple[BarrierSchedule, CounterPlacement]:
    """The jointly tuned (schedule, placement) pair for one arrival
    scatter: composition x strategy through one compiled sweep (used by
    the 5G ``sync="placed"`` mode).  Because leaf-local is in the
    strategy set, the placed winner can only match or beat the
    placement-free tuned schedule on the tuning draws.  ``objective``
    selects the tuning metric as in :func:`best_schedule`."""
    schedules = all_schedules(n_pes, cfg, prune=prune, partial=partial)
    res = tune_barrier(key, n_pes, delays=(delay,), n_trials=n_trials,
                       cfg=cfg, schedules=schedules, placements=placements,
                       core=core)
    i = int(jnp.argmin(_objective_grid(res, objective)[:, 0]))
    return res.schedules[i], res.placements[i]


# ---------------------------------------------------------------------------
# Workload-conditioned tuning: measured arrival distributions as the
# tuning axis (the Fig. 5/6 kernels + the 5G epochs), not uniform delays.
# ---------------------------------------------------------------------------

class WorkloadPoint(NamedTuple):
    """The winning schedule (+ placement) for one kernel's measured
    arrival distribution."""

    kernel: str
    schedule: BarrierSchedule
    mean_span: float              # its Fig. 4a metric on these arrivals
    uniform_schedule: BarrierSchedule   # best baseline-placed uniform radix
    uniform_span: float
    placement: object = None      # CounterPlacement | None of the winner


def sweep_workloads(key, kernels: Sequence[str] | None = None,
                    n_pes: int | None = None, n_trials: int = 8,
                    cfg: TeraPoolConfig = DEFAULT, *,
                    prune: str = "none",
                    schedules: Sequence[BarrierSchedule] | None = None,
                    placements: Sequence[str] | None = None,
                    core: str | None = None,
                    trial_chunk: int | None = None,
                    shard: bool = True,
                    faults=None,
                    fault_model=None) -> sweep.ArrivalSweepResult:
    """Sweep every kernel's MEASURED arrival distribution across the
    schedule (x placement) stack in one compiled call.

    Each kernel in ``kernels`` (default: the full Fig. 5/6 suite,
    :data:`repro.core.workloads.FIG6_KERNELS`) contributes an
    ``(n_trials, N)`` batch from :func:`repro.core.workloads.
    arrival_batch` under its own key split; the stacked
    kernel x schedule x placement x trial grid then reuses the single
    compiled scanned core via :func:`repro.core.sweep.sweep_arrivals` —
    same one-compile property as the uniform-delay tuner, with
    data-dependent arrivals.

    ``fault_model`` (a :class:`~repro.core.workloads.PEFaultModel`)
    degrades every kernel's batch with per-PE straggles / stalls /
    fail-stops under a key folded off ``key`` — the fault-free draws
    are IDENTICAL to the no-model call, so robustness deltas isolate
    the faults.  Pair any nonzero ``p_fail`` with a finite-timeout or
    sub-1.0-quorum ``faults`` spec (otherwise the plain cores
    propagate the ``+inf`` arrivals into hung episodes)."""
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    if kernels is None:
        kernels = workloads_mod.FIG6_KERNELS
    kernels = tuple(kernels)
    if not kernels:
        raise ValueError("need at least one kernel to sweep")
    keys = jax.random.split(key, len(kernels))
    arrivals = jnp.stack([
        workloads_mod.arrival_batch(k, kernel, (n_trials, n), cfg=cfg)
        for k, kernel in zip(keys, kernels)])
    if fault_model is not None:
        arrivals = workloads_mod.apply_faults(
            jax.random.fold_in(key, 0x0FA17), arrivals, fault_model)
    if schedules is None:
        schedules = all_schedules(n, cfg, prune=prune)
    scheds, placs = _cross_placements(schedules, placements, cfg)
    return sweep.sweep_arrivals(arrivals, scheds, cfg, placements=placs,
                                kernels=kernels, core=core,
                                trial_chunk=trial_chunk, shard=shard,
                                faults=faults)


def best_per_kernel(res: sweep.ArrivalSweepResult) -> List[WorkloadPoint]:
    """The argmin-span (schedule, placement) for each kernel's measured
    arrivals, paired with the best baseline-placed UNIFORM radix on the
    same arrivals (the Fig. 6 per-kernel radix-selection baseline)."""
    spans = jnp.mean(res.span_cycles, axis=-1)          # (S, K)
    placs, uniform = _uniform_baseline(res)
    out = []
    for j, kernel in enumerate(res.kernels):
        col = spans[:, j]
        i, iu = _column_winners(col, uniform)
        out.append(WorkloadPoint(
            kernel=str(kernel), schedule=res.schedules[i],
            mean_span=float(col[i]),
            uniform_schedule=res.schedules[iu],
            uniform_span=float(col[iu]),
            placement=placs[i]))
    return out


def tune_for_workload(key, kernel: str, n_pes: int | None = None,
                      n_trials: int = 8, cfg: TeraPoolConfig = DEFAULT, *,
                      prune: str = "none",
                      placements: Sequence[str] | None = None,
                      core: str | None = None) -> WorkloadPoint:
    """Tune one kernel: its measured arrival batch through the full
    schedule (x placement) stack, argmin by mean span.

    Because the stack is a superset of every uniform radix (and, with
    ``placements``, of every placed point), the returned schedule can
    only match or beat both the best uniform radix AND whatever
    :func:`best_per_delay` selected on uniform scatters, when all are
    evaluated on this kernel's own arrivals — the acceptance bar of
    tests/test_workload_tuning.py."""
    res = sweep_workloads(key, (kernel,), n_pes, n_trials, cfg,
                          prune=prune, placements=placements, core=core)
    return best_per_kernel(res)[0]


def tune_for_arrivals(arrivals, cfg: TeraPoolConfig = DEFAULT, *,
                      prune: str = "none", partial: bool = False,
                      schedules: Sequence[BarrierSchedule] | None = None,
                      placements: Sequence[str] | None = None,
                      core: str | None = None,
                      objective: str = "cycles"
                      ) -> Tuple[BarrierSchedule, CounterPlacement | None,
                                 float]:
    """The winning (schedule, placement, mean_span) for an EXPLICIT
    arrival matrix ``(n_trials, N)`` — e.g. a trace of one 5G epoch, or
    a mixture of epochs stacked along the trial axis.  The 5G
    ``sync="workload"`` mode tunes each of its barriers through this.

    ``objective`` selects the winner: ``"cycles"`` (legacy argmin by
    mean span), ``"energy"``, ``"edp"``, or ``"pareto"`` (knee of the
    2-D latency x energy front).  The returned float is always the
    winner's mean span so callers can report the latency cost of a
    non-cycles pick."""
    arrivals = jnp.asarray(arrivals, jnp.float32)
    if arrivals.ndim == 1:
        arrivals = arrivals[None]
    if arrivals.ndim != 2:
        raise ValueError(
            f"expected an (n_trials, n_pes) arrival matrix, got shape "
            f"{arrivals.shape}")
    n = arrivals.shape[-1]
    if schedules is None:
        schedules = all_schedules(n, cfg, prune=prune, partial=partial)
    scheds, placs = _cross_placements(schedules, placements, cfg)
    res = sweep.sweep_arrivals(arrivals, scheds, cfg, placements=placs,
                               core=core)
    win = best_for_arrival_stack(res, (objective,))[0]
    return win.schedule, win.placement, win.mean_span


# Fixed seed for the workload tuner's arrival draws: tuning is part of
# the schedule construction, deterministic per (kernel, N, cfg).
_WORKLOAD_TUNING_SEED = 65


@functools.lru_cache(maxsize=None)
def tuned_for_workload(kernel: str, n_pes: int | None = None,
                       cfg: TeraPoolConfig = DEFAULT, *,
                       prune: str = "none", n_trials: int = 8,
                       placements: Tuple[str, ...] | None = None
                       ) -> Tuple[BarrierSchedule, CounterPlacement | None]:
    """The two-layer schedule store: the winning (schedule, placement)
    for ``kernel`` at ``(n_pes, cfg)``, tuned once under a fixed seed
    and reused by every later consumer (apps, benchmarks, examples).

    The lru cache is the in-process layer; beneath it sits the
    persistent, checksummed on-disk store of
    :mod:`repro.runtime.schedule_cache` (active when
    ``REPRO_SCHEDULE_CACHE`` is set), so a SECOND PROCESS asking for
    the same ``(kernel, n_pes, cfg)`` performs zero sweep recomputation
    — and a corrupt cache entry is detected and re-tuned, not
    trusted."""
    from ..runtime import schedule_cache
    key = ("tuned_for_workload", kernel, int(n_pes or cfg.n_pes),
           repr(cfg), prune, int(n_trials), placements)
    hit = schedule_cache.load(key)
    if hit is not None:
        return schedule_cache.decode_pair(hit, cfg)
    p = tune_for_workload(jax.random.PRNGKey(_WORKLOAD_TUNING_SEED),
                          kernel, n_pes, n_trials, cfg, prune=prune,
                          placements=placements)
    schedule_cache.store(key, schedule_cache.encode_pair(p.schedule,
                                                         p.placement))
    return p.schedule, p.placement
