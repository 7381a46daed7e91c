"""Bank-aware counter placement: WHERE every barrier counter lives.

The paper places barrier counters "local to contiguous PE blocks"
(Sec. 5); the seed model reduced that to one span-derived latency per
tree level (``topology.access_latency``), so co-located counters never
contended and the tuner could not reason about placement at all.  The
MemPool/TeraPool interconnect studies (Cavalcante et al., Riedel et
al.) show bank *conflicts* — not just hop latency — dominate shared-L1
atomics, so this module makes the counter -> bank mapping an explicit,
tunable design axis:

* :class:`CounterPlacement` — for every counter of every tree level,
  the concrete L1 bank it occupies plus the locality-class latency its
  accessors pay (derived from ``TeraPoolConfig.span_bank_latency``, not
  the span heuristic).
* Strategies (:func:`place_counters`):
    - ``leaf_local``       — the paper's Sec. 5 policy: each counter in
      the first bank of its span's first PE.  Distinct banks, minimal
      latency; reproduces the legacy 1/3/5 per-level latencies
      bit-for-bit (the backward-compat oracle).
    - ``tile_interleaved`` — counters allocated round-robin across the
      Tiles' banks (word-interleaved heap allocation): conflict-free
      but mostly cluster-class latency.
    - ``group_hub``        — every counter inside a Group lands on that
      Group's hub bank: compact notification region, heavy same-bank
      contention among sibling counters.
    - ``central``          — all counters on bank 0: the degenerate
      maximum-contention corner.
* :func:`explicit_placement` — per-level ``(offset, stride)`` bank
  encoding, the raw knob the tuner (and tests) can drive directly:
  counter ``j`` of level ``l`` sits at ``(offset[l] + j * stride[l])
  % n_banks``.
* :func:`simulate_placed_reference` — an independent numpy oracle that
  walks explicit per-bank request queues; the scanned simulator's
  per-bank serialization is validated against it
  (tests/test_placement.py).

Sibling counters mapped to one bank *contend*: their atomics enter the
same single-ported service queue, so both simulator cores serialize
requests per bank rather than per counter (see
:func:`repro.core.barrier_sim._telescope_core`, the shrinking-width
production core, and :func:`repro.core.barrier_sim._scan_core`, its
full-width oracle — the per-bank-queue semantics are identical and
both are validated against :func:`simulate_placed_reference`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .barrier import BarrierSchedule
from .topology import DEFAULT, TeraPoolConfig

# The named strategy set the tuner sweeps by default.
STRATEGIES: Tuple[str, ...] = ("leaf_local", "tile_interleaved",
                               "group_hub", "central")


@dataclasses.dataclass(frozen=True)
class CounterPlacement:
    """Concrete bank assignment for every counter of one schedule.

    ``banks[l][j]`` is the bank holding counter ``j`` of level ``l``
    (counter ``j`` serves the contiguous original-PE span
    ``[j * span_l, (j+1) * span_l)``); ``latencies[l][j]`` is the
    locality-class access latency its farthest accessor pays.  Frozen
    tuples keep the object hashable so placed level tables cache like
    plain ones.
    """

    strategy: str
    banks: tuple       # tuple[tuple[int, ...], ...], one row per level
    latencies: tuple   # tuple[tuple[int, ...], ...], same shape

    @property
    def n_levels(self) -> int:
        return len(self.banks)

    def shared_bank_counters(self) -> Tuple[int, ...]:
        """Per level, how many counters share a bank with a sibling —
        the static contention exposure of this placement."""
        out = []
        for row in self.banks:
            uniq, counts = np.unique(np.asarray(row), return_counts=True)
            out.append(int(counts[counts > 1].sum()))
        return tuple(out)


def _counter_spans(schedule: BarrierSchedule) -> List[Tuple[int, int]]:
    """Per level: (span, n_counters)."""
    return [(lvl.span, schedule.n_pes // lvl.span)
            for lvl in schedule.levels]


def _banks_leaf_local(schedule: BarrierSchedule,
                      cfg: TeraPoolConfig) -> List[List[int]]:
    bf = cfg.banking_factor
    return [[j * span * bf for j in range(count)]
            for span, count in _counter_spans(schedule)]


def _banks_tile_interleaved(schedule: BarrierSchedule,
                            cfg: TeraPoolConfig) -> List[List[int]]:
    # Round-robin across the Tiles covered by the barrier, then across
    # each Tile's banks with word stride — the bank pattern of counters
    # allocated sequentially from an interleaved heap.
    n_tiles = max(1, schedule.n_pes // cfg.pes_per_tile)
    local_banks = schedule.n_pes * cfg.banking_factor
    return [[((j % n_tiles) * cfg.banks_per_tile
              + (j // n_tiles) * cfg.banking_factor) % local_banks
             for j in range(count)]
            for _, count in _counter_spans(schedule)]


def _banks_group_hub(schedule: BarrierSchedule,
                     cfg: TeraPoolConfig) -> List[List[int]]:
    # Every counter lands on the hub bank (bank 0) of the Group holding
    # its span's first PE: a compact per-Group synchronization region.
    return [[(j * span // cfg.pes_per_group) * cfg.banks_per_group
             for j in range(count)]
            for span, count in _counter_spans(schedule)]


def _banks_central(schedule: BarrierSchedule,
                   cfg: TeraPoolConfig) -> List[List[int]]:
    return [[0] * count for _, count in _counter_spans(schedule)]


_STRATEGY_FNS: Dict[str, Callable] = {
    "leaf_local": _banks_leaf_local,
    "tile_interleaved": _banks_tile_interleaved,
    "group_hub": _banks_group_hub,
    "central": _banks_central,
}


def derive_latencies(schedule: BarrierSchedule, banks: Sequence[Sequence[int]],
                     cfg: TeraPoolConfig = DEFAULT) -> tuple:
    """Per-counter access latency from PE <-> bank locality classes.

    Counter ``j`` of level ``l`` is reached by the survivors of its
    span ``[j * span_l, (j+1) * span_l)``; the level's cost charges the
    farthest accessor's class (``cfg.span_bank_latency``), the exact
    generalization of the legacy one-latency-per-level model.
    """
    rows = []
    for (span, count), brow in zip(_counter_spans(schedule), banks):
        if len(brow) != count:
            raise ValueError(
                f"level with span {span} has {count} counters, placement "
                f"maps {len(brow)}")
        rows.append(tuple(cfg.span_bank_latency(j * span, span, int(b))
                          for j, b in enumerate(brow)))
    return tuple(rows)


@functools.lru_cache(maxsize=None)
def place_counters(schedule: BarrierSchedule, strategy: str = "leaf_local",
                   cfg: TeraPoolConfig = DEFAULT) -> CounterPlacement:
    """Map every counter of ``schedule`` to a bank under a named
    strategy and derive the per-counter access latencies.  Cached per
    (schedule, strategy, cfg) — repeated tuner sweeps over the same
    design space pay the per-counter Python derivation once.

    Partial (subset) barriers are placed in subset-relative bank
    coordinates: the 256-PE FFT subsets are span-aligned, so relative
    locality classes equal absolute ones.
    """
    try:
        fn = _STRATEGY_FNS[strategy]
    except KeyError:
        raise ValueError(
            f"unknown placement strategy {strategy!r}; "
            f"choose from {STRATEGIES}") from None
    banks = fn(schedule, cfg)
    return CounterPlacement(
        strategy=strategy,
        banks=tuple(tuple(int(b) for b in row) for row in banks),
        latencies=derive_latencies(schedule, banks, cfg))


def explicit_placement(schedule: BarrierSchedule,
                       bank_offsets: Sequence[int],
                       bank_strides: Sequence[int] | None = None,
                       cfg: TeraPoolConfig = DEFAULT) -> CounterPlacement:
    """Explicit per-level bank-offset encoding: counter ``j`` of level
    ``l`` sits at ``(bank_offsets[l] + j * bank_strides[l]) % n_banks``.

    ``bank_strides`` defaults to the banking factor (consecutive
    counters in consecutive word-interleaved banks); a stride of 0
    deliberately piles every counter of a level onto one bank.
    """
    n_levels = schedule.n_levels
    if len(bank_offsets) != n_levels:
        raise ValueError(
            f"schedule has {n_levels} levels, got {len(bank_offsets)} "
            f"bank offsets")
    if bank_strides is None:
        bank_strides = [cfg.banking_factor] * n_levels
    if len(bank_strides) != n_levels:
        raise ValueError(
            f"schedule has {n_levels} levels, got {len(bank_strides)} "
            f"bank strides")
    banks = [[(int(off) + j * int(stride)) % cfg.n_banks
              for j in range(count)]
             for (_, count), off, stride in zip(_counter_spans(schedule),
                                                bank_offsets, bank_strides)]
    return CounterPlacement(
        strategy="explicit",
        banks=tuple(tuple(row) for row in banks),
        latencies=derive_latencies(schedule, banks, cfg))


def all_placements(schedule: BarrierSchedule,
                   strategies: Sequence[str] = STRATEGIES,
                   cfg: TeraPoolConfig = DEFAULT) -> List[CounterPlacement]:
    """One :class:`CounterPlacement` per named strategy."""
    return [place_counters(schedule, s, cfg) for s in strategies]


# ---------------------------------------------------------------------------
# Independent per-bank-queue oracle (numpy, test-only).
# ---------------------------------------------------------------------------

def _placed_episode(arr: np.ndarray, schedule: BarrierSchedule,
                    pl: CounterPlacement, cfg: TeraPoolConfig) -> float:
    """One episode via explicit per-bank queues; returns the final
    survivor's ready time (float32 arithmetic, matching the scanned
    core op-for-op so equivalence is exact)."""
    svc = np.float32(cfg.bank_service_cycles)
    instr = np.float32(cfg.instr_per_level)
    ready = arr.astype(np.float32) + instr
    for lvl, brow, lrow in zip(schedule.levels, pl.banks, pl.latencies):
        g = lvl.group_size
        m = ready.shape[0]
        grp = np.arange(m) // g
        bank = np.asarray(brow, np.int64)[grp]
        done = np.empty(m // g, np.float32)
        for b in np.unique(bank):
            sel = np.nonzero(bank == b)[0]
            order = sel[np.argsort(ready[sel], kind="stable")]
            a = ready[order]
            r = np.arange(len(a), dtype=np.float32) * svc
            s = np.maximum.accumulate(a - r) + r   # per-request service start
            for gi in np.unique(grp[order]):
                mask = grp[order] == gi
                done[gi] = np.float32(s[mask].max()
                                      + np.float32(lrow[gi]))
        ready = done + instr
    return float(ready[0])


def simulate_placed_reference(arrivals, schedule: BarrierSchedule,
                              placement: CounterPlacement,
                              cfg: TeraPoolConfig = DEFAULT):
    """Placement-aware equivalence oracle for the scanned core.

    Walks the tree level by level with explicit per-bank request
    queues: all atomics mapped to one bank — across sibling counters —
    serialize in arrival order at ``bank_service_cycles`` apiece, and
    each counter's last arriver proceeds once its own request is
    serviced.  Pure numpy, per-episode Python loops: use only in tests
    and spot checks.
    """
    from .barrier_sim import BarrierResult, pe_mean
    from .energy import (DEFAULT_ENERGY, episode_energy,
                         schedule_energy_constants)
    arr = np.asarray(arrivals, np.float32)
    if arr.shape[-1] != schedule.n_pes:
        raise ValueError(
            f"arrivals has {arr.shape[-1]} PEs, schedule expects "
            f"{schedule.n_pes}")
    batch = arr.shape[:-1]
    flat = arr.reshape((-1, arr.shape[-1]))
    wake = np.float32(cfg.wakeup_cycles)
    exits = np.asarray(
        [_placed_episode(a, schedule, placement, cfg) for a in flat],
        np.float32) + wake
    exit_time = exits.reshape(batch)
    last = np.max(flat, axis=-1).reshape(batch)
    # Same values in, same fixed-order mean as the cores — so the
    # residency-derived energy column agrees to the same precision as
    # the exit times themselves.
    resid = pe_mean(jnp.asarray(exits[:, None] - flat)).reshape(batch)
    stat, act, idle = schedule_energy_constants(
        schedule, placement, cfg, DEFAULT_ENERGY)
    zeros = jnp.zeros(batch, jnp.int32)
    return BarrierResult(
        exit_time=jnp.asarray(exit_time),
        last_arrival=jnp.asarray(last),
        span_cycles=jnp.asarray(exit_time - last),
        mean_residency=resid,
        energy=episode_energy(jnp.float32(stat), jnp.float32(act),
                              jnp.float32(idle), schedule.n_pes, resid),
        completed=jnp.isfinite(jnp.asarray(exit_time)),
        abandoned_pes=zeros,
        timed_out_levels=zeros,
    )
