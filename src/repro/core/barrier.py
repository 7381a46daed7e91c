"""Barrier schedules: mixed-radix trees and their algebra.

A *schedule* is the static structure of the arrival tree (Sec. 3 of the
paper): how many PEs synchronize per shared counter at every level, and
the locality class (hence latency) of each level's counters.

The primitive is :func:`mixed_radix_tree`: an arbitrary per-level
composition of group sizes whose product covers the cluster.  Every
named schedule is a point in that space:

  * ``central_counter``      -> one level of size N,
  * ``kary_tree(k)``         -> ``[first, k, k, ..., k]`` (the paper
    adapts the *first* level when ``log_k(N)`` is not an integer),
  * hierarchy-matched trees  -> e.g. ``(8, 16, 8)`` for TeraPool's
    Tile/Group/Cluster structure — the tuned design points of Sec. 5
    that beat the best uniform radix (see :mod:`repro.core.tuning`).

Schedules compose (:func:`compose`): a tree over one Tile stacked under
a tree over the Groups is again a mixed-radix tree, with spans and
latencies re-derived for the combined hierarchy.

Partial barriers synchronize a contiguous subset of the cluster (e.g. the
256 PEs sharing one FFT) using the per-Group / per-Tile wakeup registers.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .energy import DEFAULT_ENERGY, EnergyModel, schedule_energy_constants
from .topology import DEFAULT, TeraPoolConfig


@dataclasses.dataclass(frozen=True)
class Level:
    """One level of the arrival tree."""

    group_size: int   # PEs (survivors) sharing one counter at this level
    span: int         # contiguous original-PE span covered by one group
    latency: int      # access latency to this level's counters (cycles)


@dataclasses.dataclass(frozen=True)
class BarrierSchedule:
    """Static structure of one barrier instance.

    ``radix`` is the uniform radix for k-ary trees and ``0`` for a
    genuinely mixed-radix composition (no single k describes it).

    ``hw`` marks a hardware event-unit barrier
    (:func:`hw_event_unit`): the levels describe the unit's
    aggregation stages (combinational, no shared-counter atomics, no
    per-level software path) instead of counter tree levels.
    """

    n_pes: int                 # PEs synchronized by this barrier
    radix: int
    levels: tuple              # tuple[Level, ...]
    partial: bool = False      # True if a subset-of-cluster barrier
    hw: bool = False           # True if a hardware event-unit barrier

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    @property
    def sizes(self) -> tuple:
        """Per-level group sizes, leaf level first."""
        return tuple(lvl.group_size for lvl in self.levels)

    @property
    def name(self) -> str:
        """Canonical name: group sizes joined leaf-to-root, e.g.
        ``"8x16x8"`` (plus a ``p`` suffix for partial barriers)."""
        return schedule_name(self)


def _check_pow2(x: int, name: str) -> None:
    if x < 2 or (x & (x - 1)) != 0:
        raise ValueError(f"{name} must be a power of two >= 2, got {x}")


def _check_size(x: int, name: str) -> None:
    """Level sizes are any integer >= 2: non-power-of-two clusters
    (768-PE / 12-Tile, asymmetric multi-cluster shapes) factor into
    levels like 3 or 12 that the generalized telescope widths handle
    exactly (:func:`telescope_widths`)."""
    if x < 2:
        raise ValueError(f"{name} must be an integer >= 2, got {x}")


def mixed_radix_tree(sizes: Sequence[int], n_pes: int | None = None,
                     cfg: TeraPoolConfig = DEFAULT, *,
                     partial: bool = False) -> BarrierSchedule:
    """Build the arrival tree with per-level group ``sizes`` (leaf level
    first).  The whole schedule design space in one constructor: every
    ordered factorization of ``N`` into level sizes >= 2 is a valid
    tree — all uniform radices, the hierarchy-matched compositions
    (e.g. ``(8, 16, 8)`` = Tile/Group/Cluster), non-power-of-two
    factors (``(8, 12, 8)`` for a 768-PE / 12-Tile cluster) and
    hierarchical multi-cluster stacks (``(8, 16, 8, 4)`` = intra tree
    x inter-cluster tree).

    Per-level spans are cumulative products of the sizes; each level's
    counter latency follows from the locality class of its span
    (``cfg.access_latency``), exactly as for uniform trees.
    """
    sizes = tuple(int(g) for g in sizes)
    if not sizes:
        raise ValueError("schedule needs at least one level")
    for g in sizes:
        _check_size(g, "level size")
    n = math.prod(sizes)
    if n_pes is not None and int(n_pes) != n:
        raise ValueError(
            f"level sizes {sizes} cover {n} PEs, expected {n_pes}")
    if n > cfg.n_pes:
        raise ValueError(f"schedule spans {n} PEs, cluster has {cfg.n_pes}")

    levels: List[Level] = []
    span = 1
    for g in sizes:
        span *= g
        levels.append(Level(group_size=g, span=span,
                            latency=cfg.access_latency(span)))

    # A single uniform k describes the tree iff every level past the
    # first is the same size k and the (possibly adapted) first level is
    # no larger — the exact shape kary_tree produces.
    tail = sizes[-1]
    uniform = all(g == tail for g in sizes[1:]) and sizes[0] <= tail
    return BarrierSchedule(n_pes=n, radix=tail if uniform else 0,
                           levels=tuple(levels), partial=partial)


def kary_tree(radix: int, n_pes: int | None = None,
              cfg: TeraPoolConfig = DEFAULT, *,
              partial: bool = False) -> BarrierSchedule:
    """The uniform-radix arrival tree for ``n_pes`` cores.

    The tail levels are exactly radix-k — ``e`` of them, where ``e`` is
    the largest exponent with ``k**e`` dividing ``N`` — and the first
    level synchronizes the leftover ``N / k**e`` PEs (paper Sec. 3:
    "adapted ... by synchronizing a number of PEs different from the
    radix of the tree in the first step").  For power-of-two ``N`` this
    reproduces the classic ``ceil(log_k N)``-level shape bit-for-bit;
    for non-power-of-two ``N`` (e.g. 768) the odd factor lands in the
    adapted first level (``768 = 3 x 4^4`` for ``k = 4``).
    """
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    k = int(radix)
    _check_size(n, "n_pes")
    _check_size(k, "radix")
    if k > n:
        raise ValueError(f"radix {k} exceeds n_pes {n}")

    e = 0
    while n % (k ** (e + 1)) == 0:
        e += 1
    if e == 0:
        raise ValueError(f"radix {k} does not divide n_pes {n}")
    first = n // (k ** e)
    sizes: List[int] = ([k] * e if first == 1 else [first] + [k] * e)
    return mixed_radix_tree(sizes, n_pes=n, cfg=cfg, partial=partial)


def central_counter(n_pes: int | None = None,
                    cfg: TeraPoolConfig = DEFAULT) -> BarrierSchedule:
    """Linear central-counter barrier: every PE hits one shared counter."""
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    return mixed_radix_tree((n,), cfg=cfg)


def partial_barrier(group_pes: int, radix: int,
                    cfg: TeraPoolConfig = DEFAULT) -> BarrierSchedule:
    """Barrier over a contiguous subset of ``group_pes`` cores (uses the
    selective Group/Tile wakeup registers of Fig. 1b)."""
    if group_pes > cfg.n_pes:
        raise ValueError("partial barrier larger than the cluster")
    return kary_tree(radix, n_pes=group_pes, cfg=cfg, partial=True)


def _hw_segments(n: int, cfg: TeraPoolConfig) -> tuple:
    """Aggregation-stage sizes of the event unit over ``n`` PEs: the
    physical Tile / Group / cluster fan-in hierarchy, greedily factored
    so non-power-of-two counts (768, 1536, asymmetric multi-cluster
    shapes) still cover ``n`` exactly — any leftover factor becomes one
    final stage."""
    dims = [cfg.pes_per_tile, cfg.tiles_per_group, cfg.n_groups]
    if getattr(cfg, "n_clusters", 1) > 1:
        dims.append(cfg.n_clusters)
    rem = int(n)
    segs: List[int] = []
    for d in dims:
        g = math.gcd(rem, d)
        if g > 1:
            segs.append(g)
            rem //= g
    if rem > 1:
        segs.append(rem)
    return tuple(segs) if segs else (1,)


def hw_event_unit(n_pes: int | None = None,
                  cfg: TeraPoolConfig = DEFAULT) -> BarrierSchedule:
    """The hardware synchronization/event-unit barrier of Glaser et al.
    (arXiv 2004.06662), as a schedule next to the software trees.

    Each PE signals arrival with ONE store to the unit's trigger
    register (``cfg.hw_entry_instr`` cycles of software — no counter
    atomics, no polling); the unit's combinational aggregation tree
    resolves a stage per ``cfg.hw_level_cycles`` (a stage spanning
    multiple clusters pays ``lat_remote`` instead), and the root fires
    the broadcast wakeup lines, resuming every WFI-slept core at once.
    Stages follow the physical Tile/Group/cluster fan-in
    (:func:`_hw_segments`), so the schedule algebra, level tables and
    both simulator cores treat it exactly like any other schedule —
    with zero per-level software overhead and no bank serialization.
    """
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    _check_size(n, "n_pes")
    if n > cfg.n_pes:
        raise ValueError(f"schedule spans {n} PEs, cluster has {cfg.n_pes}")
    levels: List[Level] = []
    span = 1
    for g in _hw_segments(n, cfg):
        span *= g
        levels.append(Level(group_size=g, span=span,
                            latency=cfg.hw_stage_latency(span)))
    return BarrierSchedule(n_pes=n, radix=0, levels=tuple(levels), hw=True)


def all_radices(n_pes: int | None = None,
                cfg: TeraPoolConfig = DEFAULT) -> Sequence[int]:
    """Every valid uniform radix: the divisors >= 2 of ``N`` (for
    power-of-two ``N`` this is exactly the powers of two 2..N;
    ``k == N`` is the central counter)."""
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    return [k for k in range(2, n + 1) if n % k == 0]


# ---------------------------------------------------------------------------
# Schedule algebra.
# ---------------------------------------------------------------------------

def compose(*schedules: BarrierSchedule,
            cfg: TeraPoolConfig = DEFAULT,
            partial: bool = False) -> BarrierSchedule:
    """Stack schedules leaf-to-root into one tree over the product of
    their PE counts.

    ``compose(tile, groups)`` synchronizes ``tile.n_pes`` PEs per leaf
    subtree, then the survivors through ``groups``: the level sizes
    concatenate, and spans/latencies are re-derived for the combined
    hierarchy (an outer level's counters move up a locality class once
    its span crosses a Tile or Group boundary).
    """
    if not schedules:
        raise ValueError("compose needs at least one schedule")
    sizes: List[int] = []
    for s in schedules:
        sizes.extend(lvl.group_size for lvl in s.levels)
    return mixed_radix_tree(sizes, cfg=cfg, partial=partial)


def schedule_name(schedule: BarrierSchedule, placement=None) -> str:
    """Canonical, sortable name: level sizes joined leaf-to-root
    (``"8x16x8"``), with a ``p`` suffix for partial barriers and an
    ``@strategy`` suffix when a counter placement is attached (e.g.
    ``"8x16x8@leaf_local"``) — the one label format every sweep result
    and 5G report uses."""
    base = "x".join(str(g) for g in schedule.sizes)
    base = ("hw" + base) if schedule.hw else base
    base += "p" if schedule.partial else ""
    return base + (f"@{placement.strategy}" if placement else "")


def describe(schedule: BarrierSchedule) -> str:
    """One-line human description of a schedule's structure."""
    kind = ("hardware event unit" if schedule.hw
            else f"central counter" if schedule.n_levels == 1
            and schedule.levels[0].group_size == schedule.n_pes
            else f"radix-{schedule.radix} tree" if schedule.radix
            else "mixed-radix tree")
    spans = ",".join(str(lvl.span) for lvl in schedule.levels)
    lats = ",".join(str(lvl.latency) for lvl in schedule.levels)
    part = " (partial)" if schedule.partial else ""
    return (f"{schedule_name(schedule)}: {kind} over {schedule.n_pes} "
            f"PEs{part}, spans [{spans}], latencies [{lats}]")


# ---------------------------------------------------------------------------
# Padded level tables: a dense, fixed-shape encoding of any schedule.
# ---------------------------------------------------------------------------

class LevelTable(NamedTuple):
    """Dense, fixed-shape encoding of a :class:`BarrierSchedule` (and
    optionally of WHERE its counters live).

    Every tree over ``n_pes`` cores fits in ``log2(n_pes)`` levels (the
    radix-2 depth), so padding each table to that depth gives every
    schedule of a given cluster size the *same array shapes*: the
    simulator compiles once and sweeps radices as data.  Padding levels
    are the identity — ``group_size == 1`` (each survivor alone at its
    counter), zero latency and zero software overhead — so they pass
    timings through unchanged.

    ``latencies`` and ``bank_ids`` are per-COUNTER columns of width
    ``G = counter_width(n_pes)`` (the most counters any level can
    have): counter ``j`` of a level reads column ``j``.  Without an
    explicit :class:`~repro.core.placement.CounterPlacement` the
    columns encode the paper's leaf-local policy — the span-heuristic
    latency broadcast per level, and one distinct bank per counter —
    so the default tables reproduce the pre-placement model
    bit-for-bit.  Sibling counters mapped to the SAME bank id contend:
    the scanned core serializes atomics per bank, not per counter.

    ``service_cycles`` and ``entry_instr`` make the *primitive* itself
    table data: software trees carry the bank service interval and the
    barrier-entry instruction path, the hardware event unit
    (:func:`hw_event_unit`) carries zeros and its trigger-store cost —
    with a zero service interval the per-bank max-plus scan degenerates
    to the plain group max, i.e. parallel single-cycle aggregation, so
    hardware and software barriers share one compiled program.

    ``energy_static`` / ``active_cycles`` / ``idle_power`` are the
    per-episode energy scalars of :func:`repro.core.energy.
    schedule_energy_constants`; the cores combine them with the
    episode's mean residency (:func:`repro.core.energy.episode_energy`)
    so the energy column is traced data too — a different
    :class:`~repro.core.energy.EnergyModel` never recompiles anything.

    Being a NamedTuple of arrays, a table is a JAX pytree: it can be
    ``vmap``-ed over a stacked leading axis (see :func:`stack_tables`)
    and fed straight through ``lax.scan``.
    """

    group_sizes: jnp.ndarray    # (L,) int32, 1 past the real depth
    latencies: jnp.ndarray      # (L, G) float32 per counter, 0 past depth
    instr_cycles: jnp.ndarray   # (L,) float32, 0 past the real depth
    bank_ids: jnp.ndarray       # (L, G) int32 counter -> bank, distinct
                                # identity banks past the real depth
    service_cycles: jnp.ndarray  # (L,) float32 bank service interval,
                                 # 0 for hw stages and padding
    entry_instr: jnp.ndarray    # () float32 barrier-entry software path
    energy_static: jnp.ndarray  # () float32 pJ, arrival-independent
    active_cycles: jnp.ndarray  # () float32 episode instruction cycles
    idle_power: jnp.ndarray     # () float32 pJ per idle PE-cycle

    @property
    def max_levels(self) -> int:
        return self.group_sizes.shape[-1]

    @property
    def max_counters(self) -> int:
        return self.bank_ids.shape[-1]


def validate_tail_padding(table: LevelTable, *,
                          full: bool = True) -> LevelTable:
    """Assert the canonical-table invariant: identity padding (group
    size 1, zero latency, zero software overhead) appears only as a
    contiguous TAIL after the real levels.

    The telescoping simulator core's ``N / 2**i`` survivor bound relies
    on exactly this: every level before the padding tail has group size
    >= 2, so the live count at least halves per step, and once padding
    starts only the single final survivor remains.  Tables built by
    :func:`level_table` / :func:`stack_tables` satisfy it by
    construction; hand-built tables are checked here (concrete arrays
    only — traced tables inside a jit are passed through unchecked).

    ``full=False`` checks the group-size column only (the part the
    width bound depends on) and skips the per-counter latency/instr
    columns — the cheap per-call guard ``simulate_table`` applies to
    tables it did not build itself.

    The check covers power-of-two AND non-power-of-two schedules alike
    (the survivor bound is cumulative-quotient based, not ``N / 2**i``;
    see :func:`telescope_widths`), and error messages name the
    offending table row, level index and group size so a bad entry in
    a big stacked sweep is locatable directly.

    Returns the table unchanged, for call-site chaining.
    """
    if isinstance(table.group_sizes, jax.core.Tracer):
        return table
    depth = table.group_sizes.shape[-1]
    sizes = np.asarray(table.group_sizes).reshape((-1, depth))
    pad = sizes == 1
    # padding must be a suffix: no real level (g >= 2) after a g == 1
    bad = pad[:, :-1] & ~pad[:, 1:]
    if np.any(bad):
        row, lvl = (int(x) for x in np.argwhere(bad)[0])
        raise ValueError(
            f"level table row {row} has identity padding (group size 1) "
            f"at level {lvl} before a real level {lvl + 1} (group size "
            f"{int(sizes[row, lvl + 1])}); canonical tables are "
            f"tail-padded only — build them with "
            f"level_table()/stack_tables()")
    if not full:
        return table
    width = table.latencies.shape[-1]
    lat = np.asarray(table.latencies).reshape((-1, depth, width))
    ins = np.asarray(table.instr_cycles).reshape((-1, depth))
    bad = pad & (np.any(lat != 0.0, axis=-1) | (ins != 0.0))
    if np.any(bad):
        row, lvl = (int(x) for x in np.argwhere(bad)[0])
        raise ValueError(
            f"level table row {row}, padding level {lvl} (of width "
            f"{width}): identity padding levels must carry zero latency "
            f"and zero instruction overhead")
    return table


# ---------------------------------------------------------------------------
# Degradation-tolerant release semantics: timeout and quorum barriers.
# ---------------------------------------------------------------------------

class FaultSpec(NamedTuple):
    """Release semantics of a degradation-tolerant barrier, as traced
    data (a JAX pytree of scalars/rows — new thresholds never
    recompile anything).

    Every counter of every level releases at

        ``release = min(quorum_done, first_arrival + timeout_cycles)``

    * **quorum**: a counter over ``g`` children releases once
      ``ceil(quorum_frac * g)`` of them have been serviced (K-of-N
      release; ``quorum_frac == 1.0`` is the classical all-arrive
      barrier).
    * **timeout**: a watchdog armed when the counter services its FIRST
      child forces release ``timeout_cycles`` later even if the quorum
      never fills — the hardware-synchronizer bound of Glaser et al.
      (arXiv 2004.06662) against a stalled or dead child deadlocking
      the whole tree.  ``+inf`` disables it.

    Children still missing at release are *abandoned*: the subtree the
    barrier gave up on is charged to ``abandoned_pes`` and its late
    arrival can no longer block any ancestor.  With ``timeout = +inf``
    and ``quorum_frac = 1.0`` the semantics — and, in the simulator,
    the float32 results bit for bit — degenerate to the classical
    barrier.

    ``timeout_cycles`` is a scalar (every level shares the budget) or a
    per-level row aligned with the PADDED level index of the table it
    runs against.  ``e_timeout_poll`` / ``e_abandon`` carry the
    degradation energy surcharges (:func:`repro.core.energy.
    robust_episode_energy`) so the energy column stays pure table+spec
    data.
    """

    timeout_cycles: jnp.ndarray   # () or (L,) float32, +inf = never
    quorum_frac: jnp.ndarray      # () float32 in (0, 1]
    e_timeout_poll: jnp.ndarray   # () float32 pJ / watchdog release
    e_abandon: jnp.ndarray        # () float32 pJ / abandoned PE


def fault_spec(timeout_cycles=jnp.inf, quorum_frac=1.0,
               energy_model: EnergyModel = DEFAULT_ENERGY) -> FaultSpec:
    """Build a :class:`FaultSpec`, validating concrete (untraced)
    thresholds: timeouts must be ``>= 0`` and the quorum fraction in
    ``(0, 1]``."""
    t = jnp.asarray(timeout_cycles, jnp.float32)
    q = jnp.asarray(quorum_frac, jnp.float32)
    if t.ndim > 1:
        raise ValueError(
            f"timeout_cycles must be a scalar or a per-level row, got "
            f"shape {t.shape}")
    if not isinstance(t, jax.core.Tracer) and bool(jnp.any(t < 0)):
        raise ValueError(f"timeout_cycles must be >= 0, got {t}")
    if not isinstance(q, jax.core.Tracer) and not bool(
            jnp.all((q > 0) & (q <= 1))):
        raise ValueError(f"quorum_frac must be in (0, 1], got {q}")
    return FaultSpec(t, q,
                     jnp.float32(energy_model.e_timeout_poll),
                     jnp.float32(energy_model.e_abandon))


# NO_FAULTS (the degenerate spec) is materialized lazily via module
# __getattr__: building it eagerly would create jax arrays at import
# time and lock the backend's device count before entry points like
# repro.launch.dryrun get to set XLA_FLAGS.
def __getattr__(name: str):
    if name == "NO_FAULTS":
        spec = fault_spec()
        globals()["NO_FAULTS"] = spec
        return spec
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def max_depth(n_pes: int) -> int:
    """Depth of the deepest tree over ``n_pes`` cores (radix 2)."""
    return max(1, int(math.log2(n_pes)))


def counter_width(n_pes: int) -> int:
    """Most counters any level of a tree over ``n_pes`` cores can have:
    the leaf level of the radix-2 tree, ``n_pes // 2``."""
    return max(1, n_pes // 2)


def default_widths(n_pes: int, depth: int) -> tuple:
    """The conservative per-step telescope widths ``max(1, N >> i)``:
    valid for ANY canonical table over ``n_pes`` cores (every real
    level at least halves the live count, so the floor-of-halving
    bound holds for non-power-of-two ``N`` too).  Used when the stacked
    group sizes are traced data (e.g. the 5G app core) and the exact
    cumulative quotients cannot be read off on the host."""
    return tuple(max(1, n_pes >> i) for i in range(depth + 1))


def telescope_widths(table: LevelTable, n_pes: int) -> tuple | None:
    """Exact per-step entry widths for the telescoping core: entry
    ``i`` bounds the survivors alive entering step ``i``.

    For one schedule the live count entering level ``i`` is exactly
    ``N // (g_0 * ... * g_{i-1})`` (floored division composes:
    ``(N // a) // b == N // (a * b)``, and the cumulative products of a
    full schedule divide ``N`` exactly) — the *cumulative quotient*.
    For a stacked table the width is the max over stacked rows, so one
    widths tuple serves the whole sweep and the one-compile property
    is untouched.  This is far tighter than the ``N >> i`` bound for
    hierarchy-shaped stacks: a leaf level of 8 shrinks the window 8x
    in one step instead of 2x, cutting the sort volume of the unrolled
    pyramid by ~2x at N=4096 (benchmarks/bench_multicluster.py).

    Returns ``None`` for traced tables — callers then fall back to
    :func:`default_widths` inside the core.
    """
    if isinstance(table.group_sizes, jax.core.Tracer):
        return None
    n = int(n_pes)
    depth = table.group_sizes.shape[-1]
    sizes = np.asarray(table.group_sizes, np.int64).reshape((-1, depth))
    cum = np.cumprod(sizes, axis=1)
    widths = [n]
    for i in range(depth):
        widths.append(int(max(1, np.max(n // cum[:, i]))))
    return tuple(widths)


@functools.lru_cache(maxsize=None)
def _host_level_table(schedule: BarrierSchedule, max_levels: int,
                      cfg: TeraPoolConfig, placement,
                      energy_model: EnergyModel) -> LevelTable:
    """The validated table of one schedule as host (numpy) arrays:
    :func:`stack_tables` stacks these rows on the host and sends each
    stacked field to the device once."""
    n = schedule.n_pes
    width = counter_width(n)
    sizes = [lvl.group_size for lvl in schedule.levels]
    if schedule.hw:
        if placement is not None:
            raise ValueError(
                "hardware event-unit barriers have no counters to place")
        # The event unit has no software level path and no bank
        # serialization: signals aggregate combinationally per stage.
        instr = [0.0] * len(sizes)
        svc = [0.0] * len(sizes)
        entry = float(cfg.hw_entry_instr)
    else:
        instr = [float(cfg.instr_per_level)] * len(sizes)
        svc = [float(cfg.bank_service_cycles)] * len(sizes)
        entry = float(cfg.instr_per_level)
    pad = max_levels - len(sizes)
    if pad < 0:
        raise ValueError(
            f"schedule has {len(sizes)} levels, max_levels={max_levels}")

    # Identity padding for unused counter columns and padding levels:
    # zero latency, and bank ids that are distinct from every real bank
    # (and from each other) so phantom counters can never contend.
    sentinel = cfg.n_pes * cfg.banking_factor
    lat_rows: list = []
    bank_rows: list = []
    if placement is None:
        # Span-heuristic fallback (paper leaf-local): one latency per
        # level broadcast across its counters, one distinct bank each.
        for lvl in schedule.levels:
            lat_rows.append([float(lvl.latency)] * width)
            bank_rows.append([j * lvl.span * cfg.banking_factor
                              for j in range(width)])
    else:
        if placement.n_levels != len(sizes):
            raise ValueError(
                f"placement maps {placement.n_levels} levels, schedule "
                f"has {len(sizes)}")
        for lvl, lrow, brow in zip(schedule.levels, placement.latencies,
                                   placement.banks):
            count = n // lvl.span
            if len(brow) != count:
                raise ValueError(
                    f"level with span {lvl.span} has {count} counters, "
                    f"placement maps {len(brow)}")
            lat_rows.append(list(map(float, lrow))
                            + [0.0] * (width - count))
            bank_rows.append(list(brow)
                             + [sentinel + j for j in range(count, width)])
    for _ in range(pad):
        lat_rows.append([0.0] * width)
        bank_rows.append(list(range(width)))

    stat, act, idle = schedule_energy_constants(
        schedule, placement, cfg, energy_model)
    return validate_tail_padding(LevelTable(
        group_sizes=np.asarray(sizes + [1] * pad, np.int32),
        latencies=np.asarray(lat_rows, np.float32),
        instr_cycles=np.asarray(instr + [0.0] * pad, np.float32),
        bank_ids=np.asarray(bank_rows, np.int32),
        service_cycles=np.asarray(svc + [0.0] * pad, np.float32),
        entry_instr=np.asarray(entry, np.float32),
        energy_static=np.asarray(stat, np.float32),
        active_cycles=np.asarray(act, np.float32),
        idle_power=np.asarray(idle, np.float32),
    ))


@functools.lru_cache(maxsize=None)
def _level_table_cached(schedule: BarrierSchedule, max_levels: int,
                        cfg: TeraPoolConfig, placement,
                        energy_model: EnergyModel) -> LevelTable:
    """The device copy of :func:`_host_level_table`, made once per row."""
    return jax.device_put(_host_level_table(schedule, max_levels, cfg,
                                            placement, energy_model))


def level_table(schedule: BarrierSchedule, max_levels: int | None = None,
                cfg: TeraPoolConfig = DEFAULT, *, placement=None,
                energy_model: EnergyModel = DEFAULT_ENERGY) -> LevelTable:
    """Encode ``schedule`` as a padded :class:`LevelTable`.

    ``max_levels`` defaults to ``log2(schedule.n_pes)`` so that *all*
    power-of-two radices over the same cluster share one table shape —
    and hence one compiled simulator.  ``placement`` (a
    :class:`~repro.core.placement.CounterPlacement`) supplies explicit
    per-counter banks and latencies; ``None`` falls back to the legacy
    span heuristic with conflict-free banks.  ``energy_model`` prices
    the schedule's energy scalars (:mod:`repro.core.energy`); being
    table data, swapping models never recompiles a core.
    """
    if max_levels is None:
        max_levels = max_depth(schedule.n_pes)
    return _level_table_cached(schedule, int(max_levels), cfg, placement,
                               energy_model)


def stack_tables(schedules: Sequence[BarrierSchedule],
                 cfg: TeraPoolConfig = DEFAULT,
                 placements: Sequence | None = None,
                 energy_model: EnergyModel = DEFAULT_ENERGY) -> LevelTable:
    """Stack the tables of same-``n_pes`` schedules along a new leading
    axis, ready to ``vmap`` one compiled simulate over the whole radix
    (or radix x placement) sweep.  ``placements`` aligns with
    ``schedules``; ``None`` entries use the span-heuristic fallback.

    The cached rows live on the host: each field is stacked there and
    sent to the device in one transfer, not one dispatch per row."""
    if not schedules:
        raise ValueError("no schedules to stack")
    n = schedules[0].n_pes
    if any(s.n_pes != n for s in schedules):
        raise ValueError("stacked schedules must share n_pes")
    if placements is None:
        placements = [None] * len(schedules)
    if len(placements) != len(schedules):
        raise ValueError(
            f"{len(schedules)} schedules but {len(placements)} placements")
    depth = max(max_depth(n),
                max(s.n_levels for s in schedules))
    with TraceAnnotation("repro.stack_tables"):
        with TraceAnnotation("repro.stack_tables.rows",
                             rows=len(schedules)) as span:
            misses = _host_level_table.cache_info().misses
            tables = [_host_level_table(s, depth, cfg, p, energy_model)
                      for s, p in zip(schedules, placements)]
            span.set_metadata(
                misses=_host_level_table.cache_info().misses - misses)
        with TraceAnnotation("repro.stack_tables.stack") as span:
            host = LevelTable(*(np.stack(f) for f in zip(*tables)))
            stacked = jax.device_put(host)
            span.set_metadata(bytes=sum(f.nbytes for f in host))
        # Each row was fully validated when it was built; the stacked
        # check keeps only the group-size suffix test, on the host copy.
        with TraceAnnotation("repro.stack_tables.validate"):
            validate_tail_padding(host, full=False)
        return stacked
