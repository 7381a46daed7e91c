"""The plain reference: barrier episodes walked in numpy, one at a time.

This is the yardstick that decides ``correct``.  It imports nothing of
the program under test: a machine is the dictionary of a configuration
file (``bench/configs/<name>.json``), a schedule is its tuple of
per-level group sizes (leaf first), and a counter placement is a
strategy name.  Everything else -- per-level latencies, counter banks,
per-bank request queues, energy constants -- is derived here from those.

The walks are the repository's numpy oracles
(``placement.simulate_placed_reference``, ``energy.energy_reference``,
``barrier_sim.simulate_reference`` and ``simulate_robust_reference``)
copied so that a later change to the program cannot move the yardstick.
They use float32 op for op, so a correct simulator agrees bit for bit;
the fixed-order PE mean and the energy formula are jitted exactly as in
the simulator, so they compile to the same arithmetic on any backend.

Every function takes ``dtype``: ``np.float32`` is the reference, and
``ml_dtypes.bfloat16`` gives the control (the same walk one precision
lower), which a sound comparison must reject.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = np.float32
COLUMNS = ("exit_time", "last_arrival", "span_cycles", "mean_residency",
           "energy", "completed", "abandoned_pes", "timed_out_levels")
STRATEGIES = ("leaf_local", "tile_interleaved", "group_hub", "central")


# ---------------------------------------------------------------------------
# The machine, from a configuration file.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Machine:
    """Topology and timing of one shared-L1 cluster (cycles)."""

    n_pes: int
    pes_per_tile: int
    tiles_per_group: int
    n_groups: int
    banking_factor: int
    lat_tile: int
    lat_group: int
    lat_cluster: int
    bank_service_cycles: int
    instr_per_level: int
    wakeup_write: int
    wakeup_trigger: int
    wfi_resume: int
    hw_entry_instr: int
    hw_level_cycles: int

    @property
    def pes_per_group(self) -> int:
        return self.pes_per_tile * self.tiles_per_group

    @property
    def banks_per_tile(self) -> int:
        return self.pes_per_tile * self.banking_factor

    @property
    def banks_per_group(self) -> int:
        return self.pes_per_group * self.banking_factor

    @property
    def wakeup_cycles(self) -> int:
        return self.wakeup_write + self.wakeup_trigger + self.wfi_resume

    def access_latency(self, span: int) -> int:
        """Latency to a counter local to a contiguous block of ``span``
        PEs (the paper's placement of leaf counters, Sec. 5)."""
        if span <= self.pes_per_tile:
            return self.lat_tile
        if span <= self.pes_per_group:
            return self.lat_group
        return self.lat_cluster

    def span_bank_latency(self, pe_lo: int, span: int, bank: int) -> int:
        """Worst-accessor latency of PEs ``[pe_lo, pe_lo + span)`` to
        ``bank``: Tile class, Group class, else cluster class."""
        pe_hi = pe_lo + span - 1
        if (pe_lo // self.pes_per_tile == pe_hi // self.pes_per_tile
                == bank // self.banks_per_tile):
            return self.lat_tile
        if (pe_lo // self.pes_per_group == pe_hi // self.pes_per_group
                == bank // self.banks_per_group):
            return self.lat_group
        return self.lat_cluster


@dataclasses.dataclass(frozen=True)
class Energy:
    """Per-event energy costs (pJ) and idle power (pJ/cycle)."""

    e_instr: float
    e_amo_issue: float
    e_amo_hop: float
    e_hw_signal: float
    e_hw_hop: float
    e_wakeup_write: float
    e_wakeup_line: float
    e_wfi_wake: float
    p_wfi: float
    p_poll: float
    sleep: str
    e_timeout_poll: float
    e_abandon: float

    @property
    def idle_power(self) -> float:
        return self.p_wfi if self.sleep == "wfi" else self.p_poll


def machine_of(config: dict) -> Machine:
    return Machine(**config["machine"])


def energy_of(config: dict) -> Energy:
    return Energy(**config["energy"])


# ---------------------------------------------------------------------------
# The schedule space and the placements, derived from sizes alone.
# ---------------------------------------------------------------------------

def compositions(n: int) -> List[Tuple[int, ...]]:
    """Every ordered factorization of ``n`` into level sizes >= 2, in
    lexicographic order (the exhaustive tuner's stack order)."""
    def facts(rem: int):
        if rem == 1:
            yield ()
            return
        for f in range(2, rem + 1):
            if rem % f == 0:
                for rest in facts(rem // f):
                    yield (f,) + rest
    return list(facts(int(n)))


def hierarchy_compositions(m: Machine) -> List[Tuple[int, ...]]:
    """Compositions whose level spans land on every Tile and Group
    boundary: the product of the per-segment factorizations."""
    n = m.n_pes
    t = math.gcd(n, m.pes_per_tile)
    g = math.gcd(n // t, m.tiles_per_group)
    segs = [s for s in (t, g, n // (t * g)) if s > 1]
    out = [()]
    for s in segs:
        out = [head + tail for head in out for tail in compositions(s)]
    return out


def kary_sizes(radix: int, n: int) -> Tuple[int, ...]:
    """The uniform radix-k tree: ``e`` levels of k, the leftover PEs in
    an adapted first level."""
    e = 0
    while n % (radix ** (e + 1)) == 0:
        e += 1
    first = n // radix ** e
    return tuple([radix] * e if first == 1 else [first] + [radix] * e)


def name_of(sizes: Sequence[int], strategy: str | None = None) -> str:
    base = "x".join(str(g) for g in sizes)
    return base + (f"@{strategy}" if strategy else "")


def parse_name(name: str) -> Tuple[Tuple[int, ...], str | None]:
    base, _, strategy = name.partition("@")
    return tuple(int(g) for g in base.split("x")), (strategy or None)


def levels(sizes: Sequence[int], m: Machine) -> List[Tuple[int, int, int]]:
    """Per level: (group size, span, span-heuristic latency)."""
    out, span = [], 1
    for g in sizes:
        span *= g
        out.append((g, span, m.access_latency(span)))
    return out


def _counter_spans(sizes, n) -> List[Tuple[int, int]]:
    out, span = [], 1
    for g in sizes:
        span *= g
        out.append((span, n // span))
    return out


def banks(sizes: Sequence[int], strategy: str, m: Machine) -> List[List[int]]:
    """The bank of every counter of every level under a strategy."""
    n = int(np.prod(sizes))
    spans = _counter_spans(sizes, n)
    bf = m.banking_factor
    if strategy == "leaf_local":
        return [[j * span * bf for j in range(count)] for span, count in spans]
    if strategy == "tile_interleaved":
        n_tiles = max(1, n // m.pes_per_tile)
        local = n * bf
        return [[((j % n_tiles) * m.banks_per_tile + (j // n_tiles) * bf)
                 % local for j in range(count)] for _, count in spans]
    if strategy == "group_hub":
        return [[(j * span // m.pes_per_group) * m.banks_per_group
                 for j in range(count)] for span, count in spans]
    if strategy == "central":
        return [[0] * count for _, count in spans]
    raise ValueError(f"unknown placement strategy {strategy!r}")


def counter_latencies(sizes: Sequence[int], bank_rows, m: Machine
                      ) -> List[List[int]]:
    """Per counter, the latency class of its farthest accessor."""
    n = int(np.prod(sizes))
    return [[m.span_bank_latency(j * span, span, int(b))
             for j, b in enumerate(row)]
            for (span, _), row in zip(_counter_spans(sizes, n), bank_rows)]


# ---------------------------------------------------------------------------
# Shared float32 arithmetic: the fixed-order PE mean and energy formula.
# ---------------------------------------------------------------------------

_SUM_BLOCK = 32


def _sequential_sum(x):
    acc = jnp.zeros(x.shape[:-1], x.dtype)
    for k in range(x.shape[-1]):
        acc = acc + x[..., k]
    return acc


@jax.jit
def pe_mean(x):
    """Mean over the last axis: blocks of 32 (zero-padded evenly on
    both sides) summed left to right, then the block sums the same way,
    until at most 32 remain."""
    n = x.shape[-1]
    s = x
    while s.shape[-1] > _SUM_BLOCK:
        pad = -s.shape[-1] % _SUM_BLOCK
        s = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(pad // 2, pad - pad // 2)])
        s = _sequential_sum(s.reshape(s.shape[:-1] + (-1, _SUM_BLOCK)))
    return _sequential_sum(s) / jnp.asarray(n, x.dtype)


@partial(jax.jit, static_argnums=(3,))
def episode_energy(energy_static, active_cycles, idle_power, n_pes,
                   mean_residency):
    """Static events plus idle leakage over the PE-cycles spent waiting."""
    return energy_static + idle_power * (
        n_pes * mean_residency - active_cycles)


@partial(jax.jit, static_argnums=(3,))
def robust_episode_energy(energy_static, active_cycles, idle_power, n_pes,
                          mean_residency, e_timeout_poll, timed_out_levels,
                          e_abandon, abandoned_pes):
    base = episode_energy(energy_static, active_cycles, idle_power,
                          n_pes, mean_residency)
    return (base + e_timeout_poll * timed_out_levels
            + e_abandon * abandoned_pes)


def count_events(sizes: Sequence[int], lat_rows, m: Machine, e: Energy,
                 dtype=F32) -> Tuple[np.floating, np.floating]:
    """(energy_static, active_cycles) by explicit per-event loops in
    float64, rounded once.  ``lat_rows`` None prices every counter at
    its level's span-heuristic latency."""
    n = int(np.prod(sizes))
    active = 0.0
    traffic = 0.0
    for _ in range(n):
        active += m.instr_per_level
    survivors = n
    for li, (g, _, lat) in enumerate(levels(sizes, m)):
        count = survivors // g
        for c in range(count):
            lc = lat_rows[li][c] if lat_rows is not None else lat
            for _ in range(g):
                traffic += e.e_amo_issue + e.e_amo_hop * lc
        for _ in range(count):
            active += m.instr_per_level
        survivors = count
    wakeup = e.e_wakeup_write
    for _ in range(n):
        wakeup += e.e_wakeup_line
    if e.sleep == "wfi":
        for _ in range(n - 1):
            wakeup += e.e_wfi_wake
    static = e.e_instr * active + traffic + wakeup
    return dtype(static), dtype(active)


# ---------------------------------------------------------------------------
# Episode walks.
# ---------------------------------------------------------------------------

def placed_episode(arr: np.ndarray, sizes, bank_rows, lat_rows, m: Machine,
                   dtype=F32) -> np.floating:
    """One episode through explicit per-bank request queues: all atomics
    mapped to one bank serialize in arrival order at the bank's service
    interval; a counter's last arriver proceeds once its own request is
    served and the response has travelled back.  Returns the final
    survivor's ready time."""
    svc = dtype(m.bank_service_cycles)
    instr = dtype(m.instr_per_level)
    ready = arr.astype(dtype) + instr
    for g, brow, lrow in zip(sizes, bank_rows, lat_rows):
        n_here = ready.shape[0]
        grp = np.arange(n_here) // g
        bank = np.asarray(brow, np.int64)[grp]
        done = np.empty(n_here // g, dtype)
        for b in np.unique(bank):
            sel = np.nonzero(bank == b)[0]
            order = sel[np.argsort(ready[sel], kind="stable")]
            a = ready[order]
            r = np.arange(len(a), dtype=dtype) * svc
            s = np.maximum.accumulate(a - r) + r
            for gi in np.unique(grp[order]):
                mask = grp[order] == gi
                done[gi] = dtype(s[mask].max() + dtype(lrow[gi]))
        ready = done + instr
    return ready[0]


def unplaced_episode(arr: np.ndarray, sizes, m: Machine,
                     dtype=F32) -> np.floating:
    """One episode with one queue per counter at its level's
    span-heuristic latency (sort, max-plus service scan, latency and
    bookkeeping per level, then the wake-up)."""
    svc = dtype(m.bank_service_cycles)
    instr = dtype(m.instr_per_level)
    ready = arr.astype(dtype) + instr
    for g, _, lat in levels(sizes, m):
        a = np.sort(ready.reshape((-1, g)), axis=-1)
        j = np.arange(a.shape[-1], dtype=dtype) * svc
        start = np.maximum.accumulate(a - j, axis=-1) + j
        ready = start[..., -1] + dtype(lat) + instr
    return dtype(ready[0] + dtype(m.wakeup_cycles))


def _f32(x) -> np.ndarray:
    """Float columns as float32 (the control's bfloat16 values are
    exactly representable)."""
    return np.asarray(x).astype(np.float32)


def _columns(exit_time, last, resid, energy, abandoned=None, timed=None):
    batch = exit_time.shape
    zeros = np.zeros(batch, np.int32)
    return {
        "exit_time": _f32(exit_time),
        "last_arrival": _f32(last),
        "span_cycles": _f32(exit_time - last),
        "mean_residency": _f32(resid),
        "energy": _f32(energy),
        "completed": np.isfinite(np.asarray(exit_time)),
        "abandoned_pes": zeros if abandoned is None else np.asarray(abandoned),
        "timed_out_levels": zeros if timed is None else np.asarray(timed),
    }


def simulate_placed(arrivals, sizes, strategy: str, m: Machine, e: Energy,
                    dtype=F32) -> Dict[str, np.ndarray]:
    """Every column of a placed schedule over ``(..., n_pes)`` arrivals."""
    arr = np.asarray(arrivals, np.float32).astype(dtype)
    n = int(np.prod(sizes))
    if arr.shape[-1] != n:
        raise ValueError(f"arrivals have {arr.shape[-1]} PEs, schedule {n}")
    batch = arr.shape[:-1]
    flat = arr.reshape((-1, n))
    brows = banks(sizes, strategy, m)
    lrows = counter_latencies(sizes, brows, m)
    wake = dtype(m.wakeup_cycles)
    exits = np.asarray([placed_episode(a, sizes, brows, lrows, m, dtype)
                        for a in flat], dtype) + wake
    last = np.max(flat, axis=-1)
    resid = pe_mean(jnp.asarray(exits[:, None] - flat))
    static, active = count_events(sizes, lrows, m, e, dtype)
    energy = episode_energy(jnp.asarray(static), jnp.asarray(active),
                            jnp.asarray(dtype(e.idle_power)), n, resid)
    cols = _columns(exits, last, resid, energy)
    return {k: v.reshape(batch) for k, v in cols.items()}


def energy_reference(arrivals, sizes, m: Machine, e: Energy,
                     strategy: str | None = None, dtype=F32) -> np.ndarray:
    """Episode energy alone: event counting plus an episode walk (per
    bank when placed, per counter otherwise) plus the shared formula."""
    arr = np.asarray(arrivals, np.float32).astype(dtype)
    n = int(np.prod(sizes))
    batch = arr.shape[:-1]
    flat = arr.reshape((-1, n))
    if strategy is None:
        lrows = None
        exits = np.asarray([unplaced_episode(a, sizes, m, dtype)
                            for a in flat], dtype)
    else:
        brows = banks(sizes, strategy, m)
        lrows = counter_latencies(sizes, brows, m)
        exits = np.asarray([placed_episode(a, sizes, brows, lrows, m, dtype)
                            for a in flat], dtype) + dtype(m.wakeup_cycles)
    static, active = count_events(sizes, lrows, m, e, dtype)
    resid = pe_mean(jnp.asarray(exits[:, None] - flat))
    energy = episode_energy(jnp.asarray(static), jnp.asarray(active),
                            jnp.asarray(dtype(e.idle_power)), n, resid)
    return np.asarray(energy).reshape(batch)


def simulate_unplaced(arrivals, sizes, m: Machine, e: Energy,
                      dtype=F32) -> Dict[str, np.ndarray]:
    """Every column of an unplaced schedule (the seed model: one
    conflict-free counter per group at its level's span latency)."""
    arr = np.asarray(arrivals, np.float32).astype(dtype)
    n = int(np.prod(sizes))
    batch = arr.shape[:-1]
    flat = arr.reshape((-1, n))
    exits = np.asarray([unplaced_episode(a, sizes, m, dtype) for a in flat],
                       dtype)
    last = np.max(flat, axis=-1)
    resid = pe_mean(jnp.asarray(exits[:, None] - flat))
    static, active = count_events(sizes, None, m, e, dtype)
    energy = episode_energy(jnp.asarray(static), jnp.asarray(active),
                            jnp.asarray(dtype(e.idle_power)), n, resid)
    cols = _columns(exits, last, resid, energy)
    return {k: v.reshape(batch) for k, v in cols.items()}


def robust_episode(arr: np.ndarray, rows: list, m: Machine,
                   timeout_row: np.ndarray, q: float, dtype=F32) -> tuple:
    """One timeout/quorum episode: per-bank FIFO queues, per-counter
    K-of-g quorum or watchdog release (armed at the first serviced
    child), per-PE abandonment.  Returns (exit, ok per PE, levels
    released by watchdog)."""
    n = arr.size
    entry = dtype(m.instr_per_level)
    svc = dtype(m.bank_service_cycles)
    instr = dtype(m.instr_per_level)
    ready = arr.astype(dtype) + entry
    ok = np.isfinite(arr)
    timed = 0
    live = n
    for li, (g, bank_row, lat_row) in enumerate(rows):
        tmo = dtype(timeout_row[li])
        n_grp = live // g
        grp = np.arange(live) // g
        bank = bank_row[grp]
        order = np.lexsort((ready, bank))
        a = ready[order]
        b = bank[order]
        gs = grp[order]
        start = np.empty(live, dtype)
        pos = 0
        while pos < live:
            end = pos
            while end < live and b[end] == b[pos]:
                end += 1
            r = np.arange(end - pos, dtype=dtype) * svc
            start[pos:end] = np.maximum.accumulate(a[pos:end] - r) + r
            pos = end
        k = int(min(max(float(np.ceil(np.float32(q) * np.float32(g))), 1.0),
                    float(g)))
        done = np.empty(n_grp, dtype)
        ab_lane = np.zeros(live, bool)
        fired = False
        for j in range(n_grp):
            sel = np.where(gs == j)[0]
            s_g = start[sel]
            qstart = dtype(np.max(s_g[:k]))
            deadline = dtype(dtype(np.min(s_g)) + tmo)
            release = min(qstart, deadline)
            if deadline < qstart:
                fired = True
            done[j] = dtype(release + dtype(lat_row[j]))
            ab_lane[order[sel[s_g > release]]] = True
        span = n // live
        for lane in np.nonzero(ab_lane)[0]:
            ok[lane * span:(lane + 1) * span] = False
        timed += int(fired)
        ready = done + instr
        live = n_grp
    return dtype(ready[0] + dtype(m.wakeup_cycles)), ok, timed


def simulate_robust(arrivals, sizes, m: Machine, e: Energy, *,
                    timeout_cycles: float, quorum_frac: float,
                    strategy: str | None = None,
                    dtype=F32) -> Dict[str, np.ndarray]:
    """Every column of a timeout/quorum barrier.  Without a strategy
    every counter has a bank of its own at its level's span latency."""
    arr = np.asarray(arrivals, np.float32).astype(dtype)
    n = int(np.prod(sizes))
    batch = arr.shape[:-1]
    flat = arr.reshape((-1, n))
    if strategy is None:
        lrows = None
        rows, survivors = [], n
        for g, _, lat in levels(sizes, m):
            count = survivors // g
            rows.append((g, np.arange(count, dtype=np.int64),
                         np.full(count, lat, dtype)))
            survivors = count
    else:
        brows = banks(sizes, strategy, m)
        lrows = counter_latencies(sizes, brows, m)
        rows = [(g, np.asarray(b, np.int64), np.asarray(lt, dtype))
                for g, b, lt in zip(sizes, brows, lrows)]
    timeout_row = np.full(len(sizes), np.float32(timeout_cycles), np.float32)
    q = float(np.float32(quorum_frac))
    walks = [robust_episode(a, rows, m, timeout_row, q, dtype) for a in flat]
    exits = np.asarray([w[0] for w in walks], dtype)
    oks = np.stack([w[1] for w in walks])
    timed = np.asarray([w[2] for w in walks], np.int32)
    arr_j = jnp.asarray(flat)
    last = jnp.max(jnp.where(jnp.isfinite(arr_j), arr_j, -jnp.inf), axis=-1)
    n_ok = jnp.sum(jnp.asarray(oks), axis=-1)
    abandoned = jnp.int32(n) - n_ok
    resid = pe_mean(jnp.where(jnp.asarray(oks),
                              jnp.asarray(exits)[:, None] - arr_j, 0))
    mean_res = resid * (jnp.asarray(n, resid.dtype)
                        / jnp.maximum(n_ok, 1).astype(resid.dtype))
    static, active = count_events(sizes, lrows, m, e, dtype)
    energy = robust_episode_energy(
        jnp.asarray(static), jnp.asarray(active),
        jnp.asarray(dtype(e.idle_power)), n, mean_res,
        jnp.asarray(dtype(e.e_timeout_poll)), timed.astype(dtype),
        jnp.asarray(dtype(e.e_abandon)), np.asarray(abandoned).astype(dtype))
    cols = _columns(exits, np.asarray(last), mean_res, energy,
                    abandoned=abandoned, timed=timed)
    return {k: np.asarray(v).reshape(batch) for k, v in cols.items()}
