"""Per-barrier energy accounting: the second objective axis.

The paper tunes barriers for cycles only; its own lineage argues the
real objective is joint latency x energy.  Glaser et al.
("Energy-Efficient Hardware-Accelerated Synchronization for
Shared-L1-Memory Multiprocessor Clusters", arXiv 2004.06662) show a
dedicated synchronization/event unit with WFI sleep beats software
barriers on BOTH axes; MemPool (arXiv 2303.17742) is the shared-L1
substrate TeraPool scales up.  This module prices one barrier episode
in picojoules under an explicit per-event cost model so the sweep and
tuner can trade cycles against energy (:func:`repro.core.tuning.
pareto_front`).

An episode's energy decomposes into a *static* part — fixed by the
schedule, placement, machine config and cost model, independent of the
arrival draw — and a *dynamic* idle-wait part proportional to the time
PEs spend inside the barrier:

* **instruction energy** — every active software cycle (barrier entry,
  per-level compare/branch/reset bookkeeping of the survivors) costs
  ``e_instr``; the hardware event unit replaces all of it with one
  trigger-register store (``cfg.hw_entry_instr`` cycles per PE).
* **atomic RMW traffic** — each fetch&add costs ``e_amo_issue`` at the
  bank plus ``e_amo_hop`` per cycle of interconnect distance, so the
  locality class of every counter (Tile / Group / cluster /
  ``lat_remote``) prices its accesses: one remote-cluster atomic costs
  ~5x a Group-local one in nJ just as it does in cycles.  Hardware
  arrival signals are dedicated wires (``e_hw_signal`` +
  ``e_hw_hop`` x stage latency), not L1 accesses.
* **wakeup fan-out** — one wakeup-register write, one hardwired line
  toggle per PE, and a WFI resume per sleeping core.
* **idle wait** — every PE-cycle inside the barrier not spent executing
  instructions is spent waiting (WFI-slept, or stalled on a pending
  atomic response — Snitch's scoreboard clock-gates the core either
  way) and leaks ``p_wfi`` per cycle; a polling barrier instead burns
  ``p_poll`` on its spin loop (``sleep="poll"``).

The split is what keeps the JAX cores bit-for-bit reproducible: the
static part and the episode's *active* instruction-cycle count are
host-side scalars baked into the
:class:`~repro.core.barrier.LevelTable` (so different cost models are
still ONE compiled program — the constants are traced data), and the
dynamic part is derived inside the core from ``mean_residency``, a
quantity every implementation already computes identically:

    energy = energy_static
             + idle_power * (n * mean_residency - active_cycles)

:func:`energy_reference` recomputes all of it independently — explicit
per-event counting loops plus a numpy per-bank-queue episode walk —
and is the oracle the JAX energy columns are validated against
bit-for-bit (tests/test_energy.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .topology import DEFAULT, TeraPoolConfig


@dataclasses.dataclass(frozen=True)
class EnergyModel:
    """Per-event energy costs (pJ) and idle power (pJ/cycle).

    The defaults are scaled to 22FDX-class numbers in the spirit of
    Glaser et al. (arXiv 2004.06662) — an integer core cycle ~1 pJ, an
    L1 atomic a full round trip incl. the read-modify-write at the bank
    (~15x a core cycle) plus distance, deep clock-gated WFI leaking
    ~0.2% of active power — chosen for realistic *ratios*, not absolute
    calibration; re-fit the fields for a different node.  The
    issue-vs-idle balance is what opens the latency x energy trade:
    every extra tree level costs a round of counter RMWs, every extra
    span cycle costs idle leakage, so deep hierarchy-matched trees win
    cycles while wide shallow trees win energy
    (:func:`repro.core.tuning.pareto_front`).  Frozen + hashable so a
    model can key the level-table cache like the config does.
    """

    e_instr: float = 1.0        # pJ / active instruction cycle
    e_amo_issue: float = 15.0   # pJ / atomic round trip incl. bank RMW
    e_amo_hop: float = 1.5      # pJ / cycle of interconnect distance
    e_hw_signal: float = 0.4    # pJ / event-unit arrival signal
    e_hw_hop: float = 0.2      # pJ / cycle of signal distance
    e_wakeup_write: float = 12.0   # pJ, wakeup-register write (AXI)
    e_wakeup_line: float = 0.6     # pJ / PE wakeup-line toggle
    e_wfi_wake: float = 5.0        # pJ / WFI resume of one core
    p_wfi: float = 0.002       # pJ / cycle, clock-gated in WFI / stalled
    p_poll: float = 0.6        # pJ / cycle, spin-polling the counter
    sleep: str = "wfi"         # "wfi" | "poll"
    # Degradation-tolerant barriers (timeout/quorum release): a level
    # that releases by watchdog pays one deadline-check + abandon-mark
    # round at its counters; every PE the tree gives up on pays the
    # cleanup cost of invalidating its pending arrival.
    e_timeout_poll: float = 8.0   # pJ / level released by watchdog
    e_abandon: float = 25.0       # pJ / abandoned PE (cleanup traffic)

    @property
    def idle_power(self) -> float:
        """pJ per idle PE-cycle under the selected wait policy."""
        if self.sleep not in ("wfi", "poll"):
            raise ValueError(
                f"unknown sleep policy {self.sleep!r}; 'wfi' or 'poll'")
        return self.p_wfi if self.sleep == "wfi" else self.p_poll


DEFAULT_ENERGY = EnergyModel()


def _level_counts(schedule):
    """Per level: (level, survivors entering, counters)."""
    m = schedule.n_pes
    out = []
    for lvl in schedule.levels:
        count = m // lvl.group_size
        out.append((lvl, m, count))
        m = count
    return out


def schedule_energy_constants(schedule, placement=None,
                              cfg: TeraPoolConfig = DEFAULT,
                              model: EnergyModel = DEFAULT_ENERGY
                              ) -> tuple:
    """The three per-episode scalars the simulator cores carry in the
    level table: ``(energy_static, active_cycles, idle_power)``.

    * ``active_cycles`` — total instruction cycles across all PEs:
      ``n`` barrier entries plus each level's survivors' bookkeeping
      (software trees), or ``n`` trigger-register stores (hardware).
    * ``energy_static`` — instruction energy + atomic/signal traffic
      (per counter, at its placement-derived access latency) + the
      wakeup fan-out.  Fixed by the schedule; arrival-independent.
    * ``idle_power`` — pJ per idle PE-cycle; multiplies
      ``n * mean_residency - active_cycles`` inside the core.

    Computed in float64 and rounded ONCE to float32, so every
    implementation (scan, telescope, references, sweeps) that consumes
    these exact scalars produces bit-for-bit identical energy columns.
    """
    n = schedule.n_pes
    hw = bool(getattr(schedule, "hw", False))
    if hw and placement is not None:
        raise ValueError(
            "hardware event-unit barriers have no counters to place")

    if hw:
        active = float(n * cfg.hw_entry_instr)
        traffic = sum(
            m * (model.e_hw_signal + model.e_hw_hop * lvl.latency)
            for lvl, m, _ in _level_counts(schedule))
    else:
        active = float(n * cfg.instr_per_level)
        traffic = 0.0
        for li, (lvl, m, count) in enumerate(_level_counts(schedule)):
            lats = (np.asarray(placement.latencies[li], np.float64)
                    if placement is not None
                    else np.full(count, float(lvl.latency)))
            traffic += lvl.group_size * (
                model.e_amo_issue * count + model.e_amo_hop * lats.sum())
            active += count * cfg.instr_per_level

    wakeup = model.e_wakeup_write + n * model.e_wakeup_line
    if model.sleep == "wfi":
        wakeup += (n - 1) * model.e_wfi_wake

    static = model.e_instr * active + traffic + wakeup
    return (np.float32(static), np.float32(active),
            np.float32(model.idle_power))


@partial(jax.jit, static_argnums=(3,))
def episode_energy(energy_static, active_cycles, idle_power, n_pes,
                   mean_residency):
    """The shared energy formula, in the one op order every
    implementation uses: static events + idle leakage over the
    PE-cycles spent waiting (total residency minus active cycles).

    Jitted on purpose: XLA contracts the multiply-adds into FMAs, so an
    eager caller (the reference oracles) would land one ulp off the
    jitted cores.  Routing every implementation through this one
    compiled formula keeps the energy column bit-for-bit identical
    everywhere (inside an outer jit the call inlines into the same
    contraction)."""
    return energy_static + idle_power * (
        n_pes * mean_residency - active_cycles)


@partial(jax.jit, static_argnums=(3,))
def robust_episode_energy(energy_static, active_cycles, idle_power, n_pes,
                          mean_residency, e_timeout_poll, timed_out_levels,
                          e_abandon, abandoned_pes):
    """:func:`episode_energy` plus the degradation surcharges: one
    watchdog-release round per timed-out level, one cleanup round per
    abandoned PE.  Jitted for the same reason as the base formula — one
    compiled op order shared by cores and oracles — and built ON TOP of
    it so a zero-fault episode (``timed_out_levels == 0``,
    ``abandoned_pes == 0``) reproduces the plain energy column bit for
    bit (``x + c*0 == x`` in IEEE-754 for the finite, positive energies
    involved)."""
    base = episode_energy(energy_static, active_cycles, idle_power,
                          n_pes, mean_residency)
    return (base + e_timeout_poll * timed_out_levels
            + e_abandon * abandoned_pes)


# ---------------------------------------------------------------------------
# Independent numpy oracle (test-only).
# ---------------------------------------------------------------------------

def _count_events(schedule, placement, cfg: TeraPoolConfig,
                  model: EnergyModel) -> tuple:
    """Explicit per-event counting loops — deliberately dumb and
    closed-form-free, the independent cross-check of
    :func:`schedule_energy_constants` (float64, rounded once)."""
    n = schedule.n_pes
    active = 0.0
    traffic = 0.0
    if getattr(schedule, "hw", False):
        for _ in range(n):
            active += cfg.hw_entry_instr
        for lvl, m, _ in _level_counts(schedule):
            for _ in range(m):
                traffic += model.e_hw_signal + model.e_hw_hop * lvl.latency
    else:
        for _ in range(n):
            active += cfg.instr_per_level
        for li, (lvl, m, count) in enumerate(_level_counts(schedule)):
            for c in range(count):
                lat = (placement.latencies[li][c]
                       if placement is not None else lvl.latency)
                for _ in range(lvl.group_size):
                    traffic += model.e_amo_issue + model.e_amo_hop * lat
            for _ in range(count):
                active += cfg.instr_per_level
    wakeup = model.e_wakeup_write
    for _ in range(n):
        wakeup += model.e_wakeup_line
    if model.sleep == "wfi":
        for _ in range(n - 1):
            wakeup += model.e_wfi_wake
    static = model.e_instr * active + traffic + wakeup
    return np.float32(static), np.float32(active)


def _episode_exit(arr: np.ndarray, schedule, cfg: TeraPoolConfig) -> float:
    """Unplaced episode walk in numpy, op-for-op the float32 sequence of
    :func:`repro.core.barrier_sim.simulate_reference` (sort, max-plus
    service scan, per-level latency + bookkeeping, wakeup)."""
    hw = bool(getattr(schedule, "hw", False))
    entry = cfg.hw_entry_instr if hw else cfg.instr_per_level
    svc = np.float32(0.0 if hw else cfg.bank_service_cycles)
    instr = np.float32(0.0 if hw else cfg.instr_per_level)
    ready = arr.astype(np.float32) + np.float32(entry)
    for lvl in schedule.levels:
        a = np.sort(ready.reshape((-1, lvl.group_size)), axis=-1)
        j = np.arange(a.shape[-1], dtype=np.float32) * svc
        start = np.maximum.accumulate(a - j, axis=-1) + j
        done = start[..., -1] + np.float32(lvl.latency)
        ready = done + instr
    return float(ready[0] + np.float32(cfg.wakeup_cycles))


def energy_reference(arrivals, schedule, cfg: TeraPoolConfig = DEFAULT,
                     placement=None,
                     model: EnergyModel = DEFAULT_ENERGY) -> jnp.ndarray:
    """Independent numpy energy oracle for one barrier episode (or a
    leading batch): explicit event-counting loops for the static part,
    an explicit per-episode queue walk (per-BANK queues when a
    placement is given) for the exit times, and the shared
    :func:`episode_energy` formula on top.  Pure python/numpy episode
    loops — test-only.
    """
    arr = np.asarray(arrivals, np.float32)
    if arr.shape[-1] != schedule.n_pes:
        raise ValueError(
            f"arrivals has {arr.shape[-1]} PEs, schedule expects "
            f"{schedule.n_pes}")
    n = schedule.n_pes
    batch = arr.shape[:-1]
    flat = arr.reshape((-1, n))

    static, active = _count_events(schedule, placement, cfg, model)
    idle = np.float32(model.idle_power)

    if placement is None:
        exits = np.asarray([_episode_exit(a, schedule, cfg) for a in flat],
                           np.float32)
    else:
        from .placement import _placed_episode
        exits = np.asarray(
            [_placed_episode(a, schedule, placement, cfg) for a in flat],
            np.float32) + np.float32(cfg.wakeup_cycles)

    # The residency mean mirrors the cores' reduction (same values in,
    # same fixed-order mean out) so the final f32 ops agree bit for bit.
    from .barrier_sim import pe_mean
    resid = pe_mean(jnp.asarray(exits[:, None] - flat))
    energy = episode_energy(jnp.float32(static), jnp.float32(active),
                            jnp.float32(idle), n, resid)
    return jnp.asarray(energy).reshape(batch)
