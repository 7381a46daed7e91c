"""One-compile design-space sweeps over barrier schedules and arrival
scatters.

The paper's whole result set (Figs. 4-7) is a sweep: barrier schedule x
arrival scatter x Monte-Carlo trial.  Because every schedule over one
cluster shares a padded :class:`~repro.core.barrier.LevelTable` shape,
the full grid runs through ONE jitted, ``vmap``-ed program — sweeping
the schedule knob costs one compile, not one per design point.

Entry points:

* :func:`sweep_schedules` — ANY stack of same-``n_pes`` schedules
  (uniform radices, mixed-radix compositions from
  :mod:`repro.core.tuning`, hand-built trees) x uniform-scatter delays
  x trials, all inside a single jit.  The per-delay arrivals are the
  seed's ``uniform_arrivals`` bit-for-bit (``uniform(0, d) ==
  d * uniform(0, 1)`` under one key), so results match the per-point
  seed path exactly.
* :func:`sweep_barrier` — the Fig. 4 grid: :func:`sweep_schedules`
  specialized to the uniform-radix stack.
* :func:`sweep_arrivals` — DATA-DEPENDENT arrivals: whole stacks of
  measured per-PE arrival matrices (kernel x trial, e.g. the Fig. 5/6
  workload models of :mod:`repro.core.workloads`) swept across a
  schedule (x placement) stack through the same single compile — the
  engine behind the workload-conditioned tuner
  (:func:`repro.core.tuning.sweep_workloads`).
* :func:`simulate_schedules` / :func:`simulate_radices` — fixed
  arrivals (e.g. one kernel's epoch, Fig. 6) swept across a schedule
  stack in one call.

Every entry point takes a ``core`` selector (``"telescope"`` — the
default shrinking-width pyramid — or ``"scan"``, the full-width oracle
core; see :mod:`repro.core.barrier_sim`), a ``trial_chunk`` knob that
splits the Monte-Carlo trial axis into bounded-memory chunks
(bit-for-bit identical to the unchunked grid — trials are
independent), and donates its internally built arrival blocks to the
jitted grids so big sweeps stop being memory-bound on backends with
buffer donation.  When more than one JAX device is visible the grids
are sharded with ``shard_map``: delay grids over the schedule axis
(when it divides evenly), arrival grids over a 2-D schedule x kernel
device mesh whenever that uses more devices than the schedule axis
alone — short hierarchical multi-cluster stacks with many workload
kernels still saturate every device (transparent 2-D -> 1-D ->
single-device fallback: same compiled math, same results).
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from . import barrier, barrier_sim
from .barrier import LevelTable
from .barrier_sim import BarrierResult, core_fn
from .topology import DEFAULT, TeraPoolConfig


def _stack_radices(schedules: tuple) -> jnp.ndarray:
    """(S,) uniform radix per stacked schedule (0 where mixed-radix)."""
    return jnp.asarray([s.radix for s in schedules], jnp.int32)


def _stack_names(schedules: tuple, placements: tuple) -> tuple:
    """Canonical per-point labels, ``@strategy``-suffixed where an
    explicit placement is attached (shared by both result types)."""
    placs = placements or (None,) * len(schedules)
    return tuple(barrier.schedule_name(s, p)
                 for s, p in zip(schedules, placs))


class SweepResult(NamedTuple):
    """Per-point timings over a (schedule[, placement], delay, trial)
    grid.

    Every array field is ``(n_schedules, n_delays, n_trials)``;
    ``schedules`` (static metadata) and ``delays`` echo the grid axes
    for self-describing results.  ``radices`` is the per-schedule
    uniform radix (0 for mixed-radix compositions).  ``placements``
    aligns with ``schedules`` — one
    :class:`~repro.core.placement.CounterPlacement` (or ``None`` for
    the span-heuristic fallback) per stacked design point; empty on
    placement-free sweeps.
    """

    schedules: tuple              # tuple[BarrierSchedule], length S
    delays: jnp.ndarray           # (D,) float32
    exit_time: jnp.ndarray        # (S, D, T)
    last_arrival: jnp.ndarray     # (S, D, T)
    span_cycles: jnp.ndarray      # (S, D, T)
    mean_residency: jnp.ndarray   # (S, D, T)
    energy: jnp.ndarray           # (S, D, T) episode energy, pJ
    completed: jnp.ndarray        # (S, D, T) bool: barrier released
    abandoned_pes: jnp.ndarray    # (S, D, T) int32 abandoned PEs
    timed_out_levels: jnp.ndarray  # (S, D, T) int32 watchdog releases
    placements: tuple = ()        # tuple[CounterPlacement | None], length S

    @property
    def radices(self) -> jnp.ndarray:
        """(S,) uniform radix per schedule (0 where mixed-radix)."""
        return _stack_radices(self.schedules)

    @property
    def names(self) -> tuple:
        """Canonical schedule names, e.g. ``("2x8x8x8", "8x16x8")``,
        suffixed ``@strategy`` where an explicit placement is attached."""
        return _stack_names(self.schedules, self.placements)

    @property
    def mean_span(self) -> jnp.ndarray:
        """(S, D) Fig. 4a metric, averaged over trials."""
        return jnp.mean(self.span_cycles, axis=-1)

    @property
    def mean_residency_grid(self) -> jnp.ndarray:
        """(S, D) mean per-PE barrier residency, averaged over trials."""
        return jnp.mean(self.mean_residency, axis=-1)

    @property
    def mean_energy(self) -> jnp.ndarray:
        """(S, D) episode energy (pJ), averaged over trials."""
        return jnp.mean(self.energy, axis=-1)

    @property
    def completion_rate(self) -> jnp.ndarray:
        """(S, D) mean fraction of PEs released per barrier episode
        (1.0 everywhere on fault-free sweeps)."""
        n = jnp.float32(self.schedules[0].n_pes)
        return jnp.mean(1.0 - self.abandoned_pes.astype(jnp.float32) / n,
                        axis=-1)


class ArrivalSweepResult(NamedTuple):
    """Per-point timings over a (schedule[, placement], kernel, trial)
    grid — the data-dependent sibling of :class:`SweepResult`.

    Every array field is ``(n_schedules, n_kernels, n_trials)``;
    ``kernels`` echoes the arrival-stack axis (kernel names, or
    positional labels when none were given) and ``schedules`` /
    ``placements`` align exactly as in :class:`SweepResult`.
    """

    schedules: tuple              # tuple[BarrierSchedule], length S
    kernels: tuple                # tuple[str], length K
    exit_time: jnp.ndarray        # (S, K, T)
    last_arrival: jnp.ndarray     # (S, K, T)
    span_cycles: jnp.ndarray      # (S, K, T)
    mean_residency: jnp.ndarray   # (S, K, T)
    energy: jnp.ndarray           # (S, K, T) episode energy, pJ
    completed: jnp.ndarray        # (S, K, T) bool: barrier released
    abandoned_pes: jnp.ndarray    # (S, K, T) int32 abandoned PEs
    timed_out_levels: jnp.ndarray  # (S, K, T) int32 watchdog releases
    placements: tuple = ()        # tuple[CounterPlacement | None], length S

    @property
    def radices(self) -> jnp.ndarray:
        """(S,) uniform radix per schedule (0 where mixed-radix)."""
        return _stack_radices(self.schedules)

    @property
    def names(self) -> tuple:
        """Canonical schedule names, ``@strategy``-suffixed where an
        explicit placement is attached (see :class:`SweepResult`)."""
        return _stack_names(self.schedules, self.placements)

    @property
    def mean_span(self) -> jnp.ndarray:
        """(S, K) Fig. 4a metric per kernel, averaged over trials."""
        return jnp.mean(self.span_cycles, axis=-1)

    @property
    def mean_energy(self) -> jnp.ndarray:
        """(S, K) episode energy (pJ) per kernel, averaged over trials."""
        return jnp.mean(self.energy, axis=-1)

    @property
    def completion_rate(self) -> jnp.ndarray:
        """(S, K) mean fraction of PEs released per barrier episode
        (1.0 everywhere on fault-free sweeps)."""
        n = jnp.float32(self.schedules[0].n_pes)
        return jnp.mean(1.0 - self.abandoned_pes.astype(jnp.float32) / n,
                        axis=-1)


def radix_tables(radices: Sequence[int], n_pes: int | None = None,
                 cfg: TeraPoolConfig = DEFAULT) -> LevelTable:
    """Stacked ``(R, max_levels)`` level tables for a radix sweep."""
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    scheds = [barrier.kary_tree(r, n_pes=n, cfg=cfg) for r in radices]
    return barrier.stack_tables(scheds, cfg)


def _sweep_body(tables: LevelTable, delays: jnp.ndarray, unit: jnp.ndarray,
                cfg: TeraPoolConfig, core: str,
                widths: tuple | None = None) -> BarrierResult:
    """(R, D, T) grid body (unjitted — shared by the plain jit and the
    sharded path).

    ``unit`` is a (T, n_pes) block of standard uniforms; scaling by each
    delay reproduces ``uniform_arrivals`` for that delay exactly.
    ``widths`` is the static telescope width table of the stack
    (``None`` = the conservative in-core default).
    """
    fn = core_fn(core)
    arrivals = delays[:, None, None] * unit[None, :, :]      # (D, T, N)
    per_trial = jax.vmap(lambda tab, a: fn(a, tab, cfg, widths),
                         in_axes=(None, 0))                  # over T
    per_delay = jax.vmap(per_trial, in_axes=(None, 0))       # over D
    per_radix = jax.vmap(per_delay, in_axes=(0, None))       # over R
    return per_radix(tables, arrivals)


# ``unit`` / ``arrivals`` blocks are built (or sliced) fresh by the
# sweep entry points, so the jitted grids donate them: on backends with
# buffer donation the N=1024 512-composition grids reuse the arrival
# block in place instead of holding input + output live (CPU ignores
# donation; results are identical either way).
@partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(2,))
def _sweep_grid(tables: LevelTable, delays: jnp.ndarray, unit: jnp.ndarray,
                cfg: TeraPoolConfig, core: str,
                widths: tuple | None) -> BarrierResult:
    """(R, D, T) grid through one compiled program."""
    return _sweep_body(tables, delays, unit, cfg, core, widths)


def _sweep_body_robust(tables: LevelTable, fixed: tuple, unit: jnp.ndarray,
                       cfg: TeraPoolConfig, core: str,
                       widths: tuple | None = None) -> BarrierResult:
    """(R, D, T) grid body under the degradation-tolerant cores.

    ``fixed`` packs ``(delays, fault_spec)`` into the dispatcher's
    single fixed slot; the spec (timeout rows, quorum fraction) is
    traced data broadcast across the whole grid, so sweeping it costs
    zero extra compiles."""
    delays, faults = fixed
    fn = core_fn(core, robust=True)
    arrivals = delays[:, None, None] * unit[None, :, :]      # (D, T, N)
    per_trial = jax.vmap(lambda tab, a: fn(a, tab, cfg, widths, faults),
                         in_axes=(None, 0))                  # over T
    per_delay = jax.vmap(per_trial, in_axes=(None, 0))       # over D
    per_radix = jax.vmap(per_delay, in_axes=(0, None))       # over R
    return per_radix(tables, arrivals)


@partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(2,))
def _sweep_grid_robust(tables: LevelTable, fixed: tuple, unit: jnp.ndarray,
                       cfg: TeraPoolConfig, core: str,
                       widths: tuple | None) -> BarrierResult:
    """(R, D, T) timeout/quorum grid through one compiled program."""
    return _sweep_body_robust(tables, fixed, unit, cfg, core, widths)


# ---------------------------------------------------------------------------
# Device sharding: 1-D over the schedule axis, 2-D (schedule x kernel)
# for arrival grids.
# ---------------------------------------------------------------------------

def _grid_devices(n_sched: int, shard: bool, devices=None):
    """The device tuple to shard the schedule axis over, or ``None``
    for the plain single-device path (one device, indivisible stack, or
    sharding disabled).

    ``devices`` overrides the visible-device default — the elastic
    resilient runtime (:mod:`repro.runtime.resilient_sweep`) passes its
    surviving-device tuple here so a sweep continues on a shrunken mesh
    after simulated device loss."""
    if not shard:
        return None
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) <= 1 or n_sched % len(devs) != 0:
        return None
    return tuple(devs)


def _mesh_shape(n_devices: int, n_sched: int, n_kern: int) -> tuple:
    """The (sched, kern) mesh shape for a 2-D arrival-grid sharding:
    ``ds`` divides the schedule axis, ``dk`` divides the kernel axis,
    ``ds * dk <= n_devices``, maximizing device usage and preferring
    the schedule axis on ties (its shards carry the level tables, the
    bigger per-point state).  ``(1, 1)`` means no useful sharding —
    the transparent single-device fallback.

    This is what lets a 4096-16384-PE multi-cluster grid with a SHORT
    schedule stack (a handful of hierarchical candidates) but many
    workload kernels still saturate all devices: the kernel axis picks
    up the slack the schedule axis leaves."""
    best = (1, 1, 1)                       # (used, ds, dk)
    for ds in range(1, min(n_devices, n_sched) + 1):
        if n_sched % ds:
            continue
        for dk in range(1, n_devices // ds + 1):
            if n_kern % dk:
                continue
            cand = (ds * dk, ds, dk)
            if cand > best:
                best = cand
    return best[1], best[2]


@functools.lru_cache(maxsize=None)
def _sharded_grid(devices: tuple, body: str, cfg: TeraPoolConfig,
                  core: str, widths: tuple | None):
    """Jitted ``shard_map`` of a grid body over a 1-D schedule-axis
    mesh, cached per (devices, body, cfg, core, widths) so repeated
    sweeps reuse one compiled program per shape (the one-compile
    property now holds per device topology x width table)."""
    mesh = Mesh(np.asarray(devices), ("sched",))
    fn = {"sweep": _sweep_body, "arrival": _arrival_body}[body]
    mapped = jax.shard_map(partial(fn, cfg=cfg, core=core, widths=widths),
                           mesh=mesh,
                           in_specs=(P("sched"), P(), P()),
                           out_specs=P("sched"))
    return jax.jit(mapped, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _sharded_grid_2d(devices: tuple, shape: tuple, cfg: TeraPoolConfig,
                     core: str, widths: tuple | None):
    """Jitted ``shard_map`` of the ARRIVAL grid body over a 2-D
    (schedule x kernel) device mesh: the schedule axis shards the level
    tables, the kernel axis shards the arrival stacks, and each of the
    ``ds * dk`` devices simulates its (S/ds, K/dk) block of the grid.
    Outputs are (S, K, T) arrays sharded over both leading axes."""
    ds, dk = shape
    mesh = Mesh(np.asarray(devices).reshape(ds, dk), ("sched", "kern"))
    mapped = jax.shard_map(
        partial(_arrival_body, cfg=cfg, core=core, widths=widths),
        mesh=mesh,
        in_specs=(P("sched"), P(), P("kern")),
        out_specs=P("sched", "kern"))
    return jax.jit(mapped, donate_argnums=(2,))


def _dispatch_grid(body: str, tables: LevelTable, fixed: jnp.ndarray,
                   block: jnp.ndarray, cfg: TeraPoolConfig, core: str,
                   shard: bool, devices=None) -> BarrierResult:
    """Run one grid chunk: 2-D (schedule x kernel) sharded for arrival
    grids when that uses more devices than the schedule axis alone,
    1-D schedule-sharded when several devices divide the stack, plain
    jit otherwise.  ``devices`` restricts the shardable device pool
    (see :func:`_grid_devices`).

    This is the single chokepoint every sweep path (plain AND
    resilient) funnels through, so the stack's telescope width table
    is computed exactly once per chunk here and shared by all of them.
    """
    n_sched = tables.group_sizes.shape[0]
    with TraceAnnotation("repro.sweep.widths"):
        widths = barrier.telescope_widths(tables, block.shape[-1])
    if body.endswith("_robust"):
        shard = False    # robust grids run unsharded (traced FaultSpec
        #                  in the fixed slot; no shard_map spec for it)
    # The span covers enqueueing the grid, not its device time.
    with TraceAnnotation("repro.sweep.dispatch"), \
            barrier_sim.quiet_donation():
        if body == "arrival" and shard:
            devs = (tuple(devices) if devices is not None
                    else tuple(jax.devices()))
            ds, dk = _mesh_shape(len(devs), n_sched, block.shape[0])
            if dk > 1:
                grid = _sharded_grid_2d(devs[:ds * dk], (ds, dk), cfg,
                                        core, widths)
                return grid(tables, fixed, block)
        devices = _grid_devices(n_sched, shard, devices)
        if devices is None:
            grid = {"sweep": _sweep_grid, "arrival": _arrival_grid,
                    "sweep_robust": _sweep_grid_robust,
                    "arrival_robust": _arrival_grid_robust}[body]
            return grid(tables, fixed, block, cfg, core, widths)
        return _sharded_grid(devices, body, cfg, core, widths)(
            tables, fixed, block)


def _trial_chunks(n_trials: int, trial_chunk: int | None):
    """(lo, hi) slices of the trial axis; one full slice when unset."""
    if trial_chunk is None or trial_chunk >= n_trials:
        yield 0, n_trials
        return
    if trial_chunk < 1:
        raise ValueError(f"trial_chunk must be >= 1, got {trial_chunk}")
    for lo in range(0, n_trials, trial_chunk):
        yield lo, min(lo + trial_chunk, n_trials)


def _fresh(x: jnp.ndarray, index) -> jnp.ndarray:
    """A fresh copy of one trial chunk of an input block, for the grid
    to donate."""
    with TraceAnnotation("repro.sweep.inputs"):
        return jnp.copy(x[index])


def _concat_results(parts: list) -> BarrierResult:
    if len(parts) == 1:
        return parts[0]
    return BarrierResult(*(jnp.concatenate(xs, axis=-1)
                           for xs in zip(*parts)))


def sweep_schedules(key: jax.Array,
                    schedules: Sequence[barrier.BarrierSchedule],
                    delays: Sequence[float] = (0.0, 128.0, 512.0, 2048.0),
                    n_trials: int = 16,
                    cfg: TeraPoolConfig = DEFAULT,
                    placements: Sequence | None = None, *,
                    core: str | None = None,
                    trial_chunk: int | None = None,
                    shard: bool = True,
                    devices=None,
                    faults=None) -> SweepResult:
    """Run ANY same-``n_pes`` schedule stack x delay x trial grid in one
    compiled call — uniform radices, mixed-radix compositions and
    counter placements alike flow through the same jitted program.

    ``placements`` aligns with ``schedules`` (``None`` entries fall
    back to the span heuristic); placed and unplaced points share one
    table shape, so adding the placement axis costs zero extra
    compiles.  ``core`` selects the simulator implementation
    (telescope/scan); ``trial_chunk`` bounds the live grid memory by
    splitting the trial axis (chunked == unchunked bit-for-bit; the
    trial draws happen once, up front); ``shard`` allows splitting the
    schedule axis across visible devices (``devices`` restricts the
    pool to an explicit tuple, e.g. the survivors of a device loss).

    ``faults`` — a :class:`~repro.core.barrier.FaultSpec` from
    :func:`~repro.core.barrier.fault_spec` — switches the grid to the
    degradation-tolerant cores (timeout/quorum release); the spec is
    traced data, so sweeping specs reuses one compiled robust grid."""
    schedules = tuple(schedules)
    tables = barrier.stack_tables(schedules, cfg, placements)
    n = schedules[0].n_pes
    with TraceAnnotation("repro.sweep.inputs"):
        unit = jax.random.uniform(key, (n_trials, n), jnp.float32,
                                  0.0, 1.0)
        d = jnp.asarray(delays, jnp.float32)
    core = barrier_sim.resolve_core(core)
    body = "sweep" if faults is None else "sweep_robust"
    fixed = d if faults is None else (d, faults)
    res = _concat_results([
        _dispatch_grid(body, tables, fixed, _fresh(unit, np.s_[lo:hi]), cfg,
                       core, shard, devices)
        for lo, hi in _trial_chunks(n_trials, trial_chunk)])
    # Placement-free sweeps keep the documented empty tuple (consumers
    # treat () and all-None alike via ``res.placements or ...``).
    placements = tuple(placements) if placements is not None else ()
    return SweepResult(schedules=schedules, delays=d,
                       placements=placements, **res._asdict())


def sweep_barrier(key: jax.Array, radices: Sequence[int] | None = None,
                  delays: Sequence[float] = (0.0, 128.0, 512.0, 2048.0),
                  n_pes: int | None = None, n_trials: int = 16,
                  cfg: TeraPoolConfig = DEFAULT, *,
                  core: str | None = None,
                  trial_chunk: int | None = None,
                  shard: bool = True) -> SweepResult:
    """The Fig. 4 grid: :func:`sweep_schedules` over the uniform-radix
    stack."""
    n = int(n_pes if n_pes is not None else cfg.n_pes)
    if radices is None:
        radices = barrier.all_radices(n, cfg)
    scheds = [barrier.kary_tree(r, n_pes=n, cfg=cfg) for r in radices]
    return sweep_schedules(key, scheds, delays, n_trials, cfg, core=core,
                           trial_chunk=trial_chunk, shard=shard)


def _arrival_body(tables: LevelTable, _unused: jnp.ndarray,
                  arrivals: jnp.ndarray, cfg: TeraPoolConfig,
                  core: str,
                  widths: tuple | None = None) -> BarrierResult:
    """(S, K, T) grid body of data-dependent arrivals (unjitted —
    shared by the plain jit and the sharded paths; ``_unused`` keeps
    the (tables, fixed, block) grid calling convention so both bodies
    share one dispatcher).  ``widths`` is the static telescope width
    table of the stack (``None`` = the conservative in-core default)."""
    fn = core_fn(core)
    per_trial = jax.vmap(lambda tab, a: fn(a, tab, cfg, widths),
                         in_axes=(None, 0))                  # over T
    per_kernel = jax.vmap(per_trial, in_axes=(None, 0))      # over K
    per_sched = jax.vmap(per_kernel, in_axes=(0, None))      # over S
    return per_sched(tables, arrivals)


@partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(2,))
def _arrival_grid(tables: LevelTable, _unused: jnp.ndarray,
                  arrivals: jnp.ndarray, cfg: TeraPoolConfig,
                  core: str, widths: tuple | None) -> BarrierResult:
    """(S, K, T) grid of data-dependent arrivals through one compile,
    donating the arrival block (built fresh by :func:`sweep_arrivals`)."""
    return _arrival_body(tables, _unused, arrivals, cfg, core, widths)


def _arrival_body_robust(tables: LevelTable, faults,
                         arrivals: jnp.ndarray, cfg: TeraPoolConfig,
                         core: str,
                         widths: tuple | None = None) -> BarrierResult:
    """(S, K, T) data-dependent grid body under the
    degradation-tolerant cores; the fixed slot carries the traced
    :class:`~repro.core.barrier.FaultSpec` shared by every point."""
    fn = core_fn(core, robust=True)
    per_trial = jax.vmap(lambda tab, a: fn(a, tab, cfg, widths, faults),
                         in_axes=(None, 0))                  # over T
    per_kernel = jax.vmap(per_trial, in_axes=(None, 0))      # over K
    per_sched = jax.vmap(per_kernel, in_axes=(0, None))      # over S
    return per_sched(tables, arrivals)


@partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(2,))
def _arrival_grid_robust(tables: LevelTable, faults,
                         arrivals: jnp.ndarray, cfg: TeraPoolConfig,
                         core: str, widths: tuple | None) -> BarrierResult:
    """(S, K, T) timeout/quorum arrival grid through one compile."""
    return _arrival_body_robust(tables, faults, arrivals, cfg, core,
                                widths)


def sweep_arrivals(arrivals: jnp.ndarray,
                   schedules: Sequence[barrier.BarrierSchedule],
                   cfg: TeraPoolConfig = DEFAULT,
                   placements: Sequence | None = None,
                   kernels: Sequence[str] | None = None, *,
                   core: str | None = None,
                   trial_chunk: int | None = None,
                   shard: bool = True,
                   devices=None,
                   faults=None) -> ArrivalSweepResult:
    """Sweep a stack of MEASURED arrival matrices across a schedule
    (x optional placement) stack in one compiled call.

    ``arrivals`` is ``(n_kernels, n_trials, n_pes)`` — e.g. one
    :func:`repro.core.workloads.arrival_batch` per kernel, stacked — or
    ``(n_trials, n_pes)`` for a single workload.  Unlike
    :func:`sweep_schedules`, whose grid is synthesized from uniform
    delays inside the jit, the arrivals here are *data*: any kernel's
    measured scatter (atomic-reduction tails, bimodal border imbalance,
    ...) flows through the same single compiled simulator core, so the
    whole kernel x schedule x placement x trial grid costs one compile
    (trace-count test in tests/test_workload_tuning.py).  ``core`` /
    ``trial_chunk`` / ``shard`` / ``faults`` behave as in
    :func:`sweep_schedules`; fail-stop PEs enter as ``+inf`` arrivals
    in the stacks themselves (see
    :func:`repro.core.workloads.apply_faults`).
    """
    with TraceAnnotation("repro.sweep.inputs"):
        arrivals = jnp.asarray(arrivals, jnp.float32)
    if arrivals.ndim == 2:
        arrivals = arrivals[None]
    if arrivals.ndim != 3:
        raise ValueError(
            f"arrivals must be (n_kernels, n_trials, n_pes) or "
            f"(n_trials, n_pes), got shape {arrivals.shape}")
    schedules = tuple(schedules)
    if schedules and arrivals.shape[-1] != schedules[0].n_pes:
        raise ValueError(
            f"arrivals has {arrivals.shape[-1]} PEs, schedules expect "
            f"{schedules[0].n_pes}")
    if kernels is not None and len(kernels) != arrivals.shape[0]:
        raise ValueError(
            f"{arrivals.shape[0]} arrival stacks but {len(kernels)} "
            f"kernel names")
    tables = barrier.stack_tables(schedules, cfg, placements)
    core = barrier_sim.resolve_core(core)
    n_trials = arrivals.shape[1]
    body = "arrival" if faults is None else "arrival_robust"
    # No delay axis for this body: the fixed slot is a zero-length
    # placeholder, or the traced FaultSpec on robust grids.
    fixed = jnp.zeros((0,), jnp.float32) if faults is None else faults
    res = _concat_results([
        _dispatch_grid(body, tables, fixed,
                       _fresh(arrivals, np.s_[:, lo:hi]), cfg, core,
                       shard, devices)
        for lo, hi in _trial_chunks(n_trials, trial_chunk)])
    kernels = (tuple(kernels) if kernels is not None
               else tuple(f"workload{i}" for i in range(arrivals.shape[0])))
    placements = tuple(placements) if placements is not None else ()
    return ArrivalSweepResult(schedules=schedules, kernels=kernels,
                              placements=placements, **res._asdict())


def split_kernels(res: ArrivalSweepResult) -> list:
    """Decompose a batched arrival sweep into per-kernel single-column
    :class:`ArrivalSweepResult` views (no copy beyond the slice).

    This is the provenance hook of the serving daemon
    (:mod:`repro.runtime.serving`): because the kernel axis is a plain
    vmap batch dimension, slicing column ``j`` out of a batched grid is
    bit-for-bit the result an unbatched single-kernel
    :func:`sweep_arrivals` call would return for the same trace — the
    batching acceptance bar of tests/test_serving.py."""
    return [ArrivalSweepResult(
        schedules=res.schedules, kernels=(k,), placements=res.placements,
        **{f: getattr(res, f)[:, j:j + 1]
           for f in BarrierResult._fields})
        for j, k in enumerate(res.kernels)]


@partial(jax.jit, static_argnums=(2, 3, 4))
def _schedule_stack(tables: LevelTable, arrivals: jnp.ndarray,
                    cfg: TeraPoolConfig, core: str,
                    widths: tuple | None) -> BarrierResult:
    fn = core_fn(core)
    return jax.vmap(lambda tab: fn(arrivals, tab, cfg, widths))(tables)


def simulate_schedules(arrivals: jnp.ndarray,
                       schedules: Sequence[barrier.BarrierSchedule],
                       cfg: TeraPoolConfig = DEFAULT,
                       placements: Sequence | None = None, *,
                       core: str | None = None) -> BarrierResult:
    """Simulate ONE arrival vector under every schedule (x optional
    per-entry placement) in the stack, vmapped through one compile."""
    arrivals = jnp.asarray(arrivals, jnp.float32)
    schedules = tuple(schedules)
    if schedules and arrivals.shape[-1] != schedules[0].n_pes:
        raise ValueError(
            f"arrivals has {arrivals.shape[-1]} PEs, schedules expect "
            f"{schedules[0].n_pes}")
    tables = barrier.stack_tables(schedules, cfg, placements)
    widths = barrier.telescope_widths(tables, arrivals.shape[-1])
    return _schedule_stack(tables, arrivals, cfg,
                           barrier_sim.resolve_core(core), widths)


def simulate_radices(arrivals: jnp.ndarray, radices: Sequence[int],
                     cfg: TeraPoolConfig = DEFAULT, *,
                     core: str | None = None) -> BarrierResult:
    """Simulate ONE arrival vector under every radix in ``radices``
    (Fig. 6's per-kernel radix scan), vmapped through one compile."""
    arrivals = jnp.asarray(arrivals, jnp.float32)
    scheds = [barrier.kary_tree(r, n_pes=arrivals.shape[-1], cfg=cfg)
              for r in radices]
    return simulate_schedules(arrivals, scheds, cfg, core=core)


def best_radix_per_delay(res: SweepResult) -> jnp.ndarray:
    """(D,) radix minimizing the mean Fig. 4a span at each delay.

    Only meaningful for uniform-radix stacks: mixed-radix compositions
    report radix 0.  Prefer :func:`best_schedule_per_delay` for
    arbitrary schedule stacks."""
    return res.radices[jnp.argmin(res.mean_span, axis=0)]


def best_schedule_per_delay(res: SweepResult) -> tuple:
    """(D,) canonical schedule names (``"8x16x8"``,
    ``"2x8x8x8@central"``, ...) minimizing the mean Fig. 4a span at each
    delay — the mixed-radix-safe sibling of :func:`best_radix_per_delay`
    (whose ``radix == 0`` placeholder is meaningless for mixed
    stacks)."""
    names = res.names
    return tuple(names[int(i)]
                 for i in jnp.argmin(res.mean_span, axis=0))
