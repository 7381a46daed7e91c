"""Cycle-level simulator of TeraPool barrier synchronization.

Given per-PE *arrival times* (the cycle at which each PE calls the
barrier), computes the exact timing of the arrival tree under the
machine model of :mod:`repro.core.topology`:

* every PE issues an atomic fetch&add to its group's counter;
* concurrent atomics to one BANK serialize at 1/cycle (single-ported
  bank) — modelled exactly with a max-plus prefix scan over each
  bank's request queue, so sibling counters co-located on one bank
  (see :mod:`repro.core.placement`) contend with each other;
* the group's last arriver observes ``group_size - 1``, resets the
  counter and proceeds to the next level (re-initialization is folded
  into arrival);
* the final survivor writes the memory-mapped wakeup register; the
  wakeup unit raises the hardwired lines and all sleeping PEs resume
  from WFI simultaneously.

Three implementations share the model:

* :func:`_telescope_core` — the production path (``core="telescope"``).
  The schedule is encoded as a fixed-shape, identity-padded
  :class:`~repro.core.barrier.LevelTable` and the level walk is a
  statically unrolled *telescoping pyramid*: step ``i`` touches only
  the first ``widths[i]`` lanes, where ``widths`` is the cumulative-
  quotient survivor bound of the stacked schedules
  (:func:`repro.core.barrier.telescope_widths`; the conservative
  ``max(1, N >> i)`` fallback applies when the stack is traced data).
  Because every real level has group size >= 2 and identity padding is
  tail-only (the canonicalized-table invariant,
  :func:`repro.core.barrier.validate_tail_padding`), the bound is
  sound for power-of-two and non-power-of-two compositions alike — so
  the per-level sort shrinks geometrically (or faster, for hierarchy-
  shaped stacks whose coarse leaf levels collapse the window 8-16x per
  step) and total sort work drops from ``O(N log N · log N)`` (full
  width at every level) to ``O(N log N)`` summed over levels.  Step
  shapes depend only on ``N`` and the per-stack widths tuple, never on
  which schedule in the stack is simulated, so the one-compile
  property over schedule x placement x delay grids is preserved.
* :func:`_scan_core` — the previous production path (``core="scan"``),
  a single jitted ``lax.scan`` at full width per level.  Kept as a
  bit-for-bit oracle for the telescoped core and selectable everywhere
  via ``core="scan"``.
* :func:`simulate_reference` — the original per-level Python loop,
  kept verbatim as the equivalence oracle (tests/test_sweep.py asserts
  all implementations agree bit-for-bit).

Everything is pure JAX and `vmap`-able over Monte-Carlo trials.

Fault model
-----------

Both cores have degradation-tolerant twins (``faults=`` on
:func:`simulate` / :func:`simulate_table`, dispatched to
:func:`_scan_robust_core` / :func:`_telescope_robust_core`) that model
what a real 1024-PE machine does when a PE never shows up:

* **Fail-stop** is an arrival of ``+inf`` — the same masked-lane
  convention the padded tables already use — so a per-PE fault mask is
  ordinary traced data (``fault_mask=``, applied as
  ``where(mask, +inf, arrivals)``) and composes with the
  fault-conditioned samplers of :mod:`repro.core.workloads`
  (stragglers, transient stalls) without recompiling anything.
* **Timeout release**: each counter arms a watchdog when it services
  its FIRST child and force-releases ``timeout_cycles`` later even if
  children are missing (the hardware-synchronizer bound of Glaser et
  al., arXiv 2004.06662).
* **Quorum release**: a counter over ``g`` children releases once
  ``ceil(quorum_frac * g)`` have been serviced (K-of-N semantics; for
  the central counter this is exactly K of N PEs, for trees the
  per-counter generalization).

Children still missing at a release are *abandoned*: their whole
original-PE subtree is charged to ``abandoned_pes``, and their late
arrival can no longer block any ancestor (an un-released subtree
carries ``+inf`` upward and is abandoned higher up, or — with no
timeout anywhere — deadlocks the episode: ``exit_time = +inf``,
``completed = False``).  :class:`BarrierResult` reports per episode
``completed`` / ``abandoned_pes`` / ``timed_out_levels``; span and
residency are computed over the surviving PEs.  With no faults
injected, ``timeout = +inf`` and ``quorum_frac = 1.0``, every robust
column is bit-for-bit the plain core's output (the release algebra
degenerates through IEEE identities: ``min(x, +inf) = x``,
``x * 1.0 = x``), and :func:`simulate_robust_reference` — an
independent numpy per-bank-queue walk with explicit quorum/timeout
bookkeeping — is the oracle the robust cores are validated against
bit-for-bit (tests/test_faults.py).
"""
from __future__ import annotations

import collections
import contextlib
import os
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .barrier import (BarrierSchedule, FaultSpec, LevelTable,
                      default_widths, fault_spec, level_table,
                      telescope_widths, validate_tail_padding)
from .energy import (DEFAULT_ENERGY, EnergyModel, episode_energy,
                     robust_episode_energy, schedule_energy_constants)
from .topology import DEFAULT, TeraPoolConfig


@contextlib.contextmanager
def quiet_donation():
    """The jitted simulator entry points donate their arrival blocks
    (memory-bound N=1024 grids reuse the buffer in place where the
    backend supports it); CPU has no buffer donation and would emit an
    advisory once per compile.  Wrap OUR dispatches in this scope so
    the message is silenced for the library's own calls only — never
    process-wide for unrelated user jits."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield

# Incremented once per *trace* of a simulator core ("scan_core" /
# "telescope_core"); jit caching means a whole radix x delay x trial
# sweep costs a single increment.  Tests use it to prove the
# one-compile property.
TRACE_COUNTS = collections.Counter()

# The selectable simulator cores.  "telescope" is the default hot
# path; "scan" is retained as the bit-for-bit oracle (and escape
# hatch, e.g. REPRO_BARRIER_CORE=scan).
CORES = ("telescope", "scan")
DEFAULT_CORE = os.environ.get("REPRO_BARRIER_CORE", "telescope")


def core_traces() -> int:
    """Total traces of ANY simulator core — the quantity the
    one-compile tests bound, independent of which core is active.
    Robust (fault-model) core variants count like their plain twins."""
    return sum(TRACE_COUNTS[c + "_core"] + TRACE_COUNTS[c + "_robust_core"]
               for c in CORES)


class BarrierResult(NamedTuple):
    """Timing (cycles), energy (pJ) and degradation accounting of one
    barrier episode.

    The last three columns are the fault-model telemetry.  The plain
    (fault-free) cores fill them trivially — ``completed`` is finite
    exit, zero abandonment, zero watchdog releases — so every result
    type downstream (sweeps, tuner grids, checkpoints) carries one
    uniform set of columns whether or not faults were simulated.
    """

    exit_time: jnp.ndarray        # scalar: cycle at which every PE resumes
    last_arrival: jnp.ndarray     # scalar: cycle the last PE entered
    span_cycles: jnp.ndarray      # exit_time - last_arrival  (Fig. 4a metric)
    mean_residency: jnp.ndarray   # mean over PEs of (exit - own arrival);
                                  # under faults: over the SURVIVING PEs
    energy: jnp.ndarray           # scalar: episode energy, pJ
                                  # (repro.core.energy.episode_energy)
    completed: jnp.ndarray        # bool: the barrier released (finite exit)
    abandoned_pes: jnp.ndarray    # int32: PEs the tree gave up on
                                  # (fail-stop + timeout/quorum drops)
    timed_out_levels: jnp.ndarray  # int32: levels with >= 1 watchdog release


_SUM_BLOCK = 32


def _sequential_sum(x: jnp.ndarray) -> jnp.ndarray:
    """``0 + x[..., 0] + x[..., 1] + ...`` along the last axis, one
    elementwise add at a time (XLA never reassociates float adds)."""
    acc = jnp.zeros(x.shape[:-1], x.dtype)
    for k in range(x.shape[-1]):
        acc = acc + x[..., k]
    return acc


@jax.jit
def pe_mean(x: jnp.ndarray) -> jnp.ndarray:
    """Mean over the last (PE) axis in one fixed float32 summation order.

    A plain ``jnp.mean`` is summed in whatever order the backend picks
    for the surrounding fusion, so on a TPU the jitted cores and the
    eagerly evaluated oracles land an ulp or two apart.  Here the order
    is spelled out as elementwise adds: the axis is cut into blocks of
    32 (zero-padded, the padding split evenly before and after), each
    block is summed left to right, and the block sums are reduced the
    same way until at most 32 remain.  That is exactly the order of
    XLA's CPU tree reduction, so CPU results are unchanged, and every
    backend and fusion context now gives the same bits.  Jitted so that
    the division by the constant ``n`` compiles the same way for eager
    callers (the oracles) as inside the cores."""
    n = x.shape[-1]
    s = x
    while s.shape[-1] > _SUM_BLOCK:
        pad = -s.shape[-1] % _SUM_BLOCK
        s = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(pad // 2, pad - pad // 2)])
        s = _sequential_sum(s.reshape(s.shape[:-1] + (-1, _SUM_BLOCK)))
    return _sequential_sum(s) / jnp.float32(n)


def _serialize_group(ready: jnp.ndarray, latency: int,
                     cfg: TeraPoolConfig, svc=None) -> jnp.ndarray:
    """Serialize atomics within each group (rows of ``ready``).

    ``ready[g, j]`` is the cycle PE j of group g issues its atomic.  The
    bank services one request per ``bank_service_cycles``; requests are
    served in arrival order.  Returns the completion time of the *last*
    request per group, i.e. when the last arriver has its fetched value.

    With sorted issue times a_(1..k), service start of the j-th request is
        s_j = max_{i<=j} ( a_i + (j - i) * svc )
            = j*svc + cummax( a_j - j*svc )
    — a max-plus prefix scan, fully vectorized.  ``svc`` overrides the
    config's service interval (0 for the hardware event unit, whose
    aggregation stages accept all inputs in parallel).
    """
    svc = cfg.bank_service_cycles if svc is None else svc
    a = jnp.sort(ready, axis=-1)
    j = jnp.arange(a.shape[-1], dtype=a.dtype) * svc
    start = jax.lax.cummax(a - j, axis=a.ndim - 1) + j
    # The response of the final request travels back to the last arriver.
    return start[..., -1] + latency


# ---------------------------------------------------------------------------
# Scanned core over a padded level table (the one-compile path).
# ---------------------------------------------------------------------------

def _segmented_cummax(x: jnp.ndarray, is_start: jnp.ndarray) -> jnp.ndarray:
    """Running max along the last axis that restarts wherever
    ``is_start`` is True (the classic segmented-scan combine, exact for
    max)."""
    def combine(left, right):
        lv, lf = left
        rv, rf = right
        return jnp.where(rf, rv, jnp.maximum(lv, rv)), lf | rf
    v, _ = jax.lax.associative_scan(combine, (x, is_start))
    return v


def _segment_first(is_start: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Position of each sorted element's segment start: a running max of
    the start positions.  (A ``searchsorted`` of the sorted keys into
    themselves gives the same on the CPU, but in the large vmapped
    grids on a TPU v5e it returned ranks one off in a few episodes.)"""
    return jax.lax.cummax(jnp.where(is_start, idx, 0))


def _phase(level: int, phase: str):
    """Named scope ``telescope.l<level>.<phase>`` around one phase of a
    telescope step (sort, rank, scan, segmax, compact).  It sets only
    the HLO ``op_name`` metadata, so a device trace can sum the fused
    operations per phase; the compiled program is unchanged."""
    return jax.named_scope(f"telescope.l{level}.{phase}")


def _scan_core(arrivals: jnp.ndarray, table: LevelTable,
               cfg: TeraPoolConfig, widths: tuple | None = None
               ) -> BarrierResult:
    """One barrier episode as a ``lax.scan`` over the padded level table.

    ``widths`` is accepted for signature parity with
    :func:`_telescope_core` and ignored: the scan core always runs at
    full width, which is what makes it the width-independent oracle.

    The carried state keeps a fixed shape across levels: ``ready`` is
    always ``(n_pes,)``, with the ``m`` current survivors compacted into
    the prefix ``ready[:m]`` and the tail masked to ``+inf``.

    Atomics serialize per BANK, not per counter: each survivor's
    counter (``index // g``) maps to a bank through the table's
    ``bank_ids`` column, requests are lexsorted by (bank, ready), and
    every bank's queue is one segment of the max-plus service-start
    scan — so sibling counters placed on one bank contend in a single
    shared queue, while conflict-free placements (one bank per
    counter, the default tables) reduce to the seed per-counter
    serialization bit-for-bit.  A counter's last arriver proceeds once
    its own request is serviced, plus that counter's placement-derived
    access latency (``latencies`` column).

    All shapes are fixed and every quantity (group size, banks,
    latencies) is traced data, so any schedule x placement combination
    over one cluster shares this single compiled program.  Identity
    padding levels (g=1, latency=0, instr=0, distinct banks) pass
    timings through unchanged.
    """
    n = arrivals.shape[-1]
    arrivals = jnp.asarray(arrivals, jnp.float32)
    idx = jnp.arange(n)
    width = table.bank_ids.shape[-1]

    # Level 0 entry: call, address computation, atomic issue (or, for
    # the hardware event unit, the single trigger-register store).
    ready0 = arrivals + table.entry_instr

    def step(carry, level):
        ready, m = carry
        g, lat_col, instr, bank_col, svc = level
        grp = idx // g
        # Masked tail slots can index past the counter columns; clip —
        # their +inf ready times sort to the back of any bank queue
        # they land in, so they never perturb live requests.
        bank = bank_col[jnp.minimum(grp, width - 1)]
        order = jnp.lexsort((ready, bank))
        a = ready[order]
        b = bank[order]
        gs = grp[order]
        # Per-bank queues: rank = position within the bank segment;
        # service start of request j is rank*svc + max over earlier
        # same-bank requests of (a - rank*svc) — the same max-plus
        # reduction as _serialize_group, segmented by bank.
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), b[1:] != b[:-1]])
        rank = (idx - _segment_first(is_start, idx)).astype(jnp.float32)
        start = _segmented_cummax(a - rank * svc, is_start) + rank * svc
        # The counter's last arriver is its latest-serviced request; the
        # fetched value travels back at the counter's access latency.
        last = jax.ops.segment_max(start, gs, num_segments=n)
        done = last + lat_col[jnp.minimum(idx, width - 1)]
        # Survivors run the compare/branch + counter-reset + next-level
        # setup before issuing the next atomic; compact them to the
        # prefix and re-mask the tail.
        m = m // g
        ready = jnp.where(idx < m, done + instr, jnp.inf)
        return (ready, m), None

    TRACE_COUNTS["scan_core"] += 1
    levels = (table.group_sizes, table.latencies, table.instr_cycles,
              table.bank_ids, table.service_cycles)
    (ready, _), _ = jax.lax.scan(step, (ready0, jnp.int32(n)), levels)

    exit_time = ready[0] + cfg.wakeup_cycles
    last_arrival = jnp.max(arrivals, axis=-1)
    mean_res = pe_mean(exit_time[..., None] - arrivals)
    return BarrierResult(
        exit_time=exit_time,
        last_arrival=last_arrival,
        span_cycles=exit_time - last_arrival,
        mean_residency=mean_res,
        energy=episode_energy(table.energy_static, table.active_cycles,
                              table.idle_power, n, mean_res),
        completed=jnp.isfinite(exit_time),
        abandoned_pes=jnp.int32(0),
        timed_out_levels=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# Telescoping pyramid core: statically unrolled shrinking-width steps.
# ---------------------------------------------------------------------------

def _telescope_core(arrivals: jnp.ndarray, table: LevelTable,
                    cfg: TeraPoolConfig, widths: tuple | None = None
                    ) -> BarrierResult:
    """One barrier episode as a telescoping pyramid of unrolled steps.

    Step ``i`` operates on only the first ``widths[i]`` lanes — the
    *cumulative-quotient* survivor bound of the stacked schedules
    (:func:`repro.core.barrier.telescope_widths`), or the conservative
    ``max(1, N >> i)`` of :func:`repro.core.barrier.default_widths`
    when ``widths`` is ``None`` (e.g. called with traced tables).  Any
    upper bound on the live count is sound under the canonical-table
    invariant (identity padding is tail-only, :func:`repro.core.
    barrier.validate_tail_padding`): every real level divides the live
    count by its group size ``g >= 2`` — floored division composes, so
    non-power-of-two level sizes keep the bound exact — and once
    padding starts the single final survivor trivially fits any later
    width.  Masked tail lanes inside a step's window carry ``+inf``
    exactly as in :func:`_scan_core`; lanes beyond the window hold
    only ``+inf`` phantoms, which sort to the back of their bank
    queues and never feed a live counter — so shrinking the window
    changes no live lane's float trajectory and the two cores agree
    bit for bit at every width table (tests/test_telescope.py,
    tests/test_multicluster.py).

    Inside each step the two-pass ``jnp.lexsort((ready, bank))`` of the
    scanned core becomes a single stable multi-key ``lax.sort`` over
    ``(bank, ready)`` that co-sorts the group ids; the per-bank rank
    comes from the segment starts (:func:`_segment_first`), as in the
    scanned core.

    Step widths are a STATIC tuple shared by the whole stacked sweep
    (one widths table per grid, computed host-side from the concrete
    stack); group sizes, banks and latencies stay traced data — so any
    schedule x placement combination over one stacked grid shares this
    single compiled program, exactly like the scanned core.
    """
    n = arrivals.shape[-1]
    arrivals = jnp.asarray(arrivals, jnp.float32)
    width = table.bank_ids.shape[-1]
    depth = table.group_sizes.shape[-1]

    if widths is None:
        widths = default_widths(n, depth)
    if len(widths) != depth + 1:
        raise ValueError(
            f"widths table has {len(widths)} entries for a depth-"
            f"{depth} table; need depth + 1")

    TRACE_COUNTS["telescope_core"] += 1

    # Level 0 entry: call, address computation, atomic issue (or, for
    # the hardware event unit, the single trigger-register store).
    ready = arrivals + table.entry_instr
    m = jnp.int32(n)
    for i in range(depth):
        w = min(int(widths[i]), n)
        ready = ready[:w]
        idx = jnp.arange(w)
        g = table.group_sizes[i]
        svc = table.service_cycles[i]
        grp = idx // g
        # Masked tail slots can index past the counter columns; clip —
        # their +inf ready times sort to the back of any bank queue
        # they land in, so they never perturb live requests.
        bank = table.bank_ids[i][jnp.minimum(grp, width - 1)]
        with _phase(i, "sort"):
            b, a, gs = jax.lax.sort((bank, ready, grp), num_keys=2)
        # Per-bank queues: the sorted bank column's first occurrence of
        # each bank is its segment start, so rank = idx - first.
        with _phase(i, "rank"):
            is_start = jnp.concatenate(
                [jnp.ones((1,), bool), b[1:] != b[:-1]])
            rank = (idx - _segment_first(is_start, idx)).astype(
                jnp.float32)
        with _phase(i, "scan"):
            start = (_segmented_cummax(a - rank * svc, is_start)
                     + rank * svc)
        # The counter's last arriver is its latest-serviced request; the
        # fetched value travels back at the counter's access latency.
        with _phase(i, "segmax"):
            last = jax.ops.segment_max(start, gs, num_segments=w)
            done = last + table.latencies[i][jnp.minimum(idx, width - 1)]
        # Survivors run the compare/branch + counter-reset + next-level
        # setup, then compact into the next (shrunken) window.
        m = m // g
        w_next = min(int(widths[i + 1]), w)
        with _phase(i, "compact"):
            ready = jnp.where(jnp.arange(w_next) < m,
                              done[:w_next] + table.instr_cycles[i],
                              jnp.inf)

    exit_time = ready[0] + cfg.wakeup_cycles
    last_arrival = jnp.max(arrivals, axis=-1)
    mean_res = pe_mean(exit_time[..., None] - arrivals)
    return BarrierResult(
        exit_time=exit_time,
        last_arrival=last_arrival,
        span_cycles=exit_time - last_arrival,
        mean_residency=mean_res,
        energy=episode_energy(table.energy_static, table.active_cycles,
                              table.idle_power, n, mean_res),
        completed=jnp.isfinite(exit_time),
        abandoned_pes=jnp.int32(0),
        timed_out_levels=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# Degradation-tolerant (robust) cores: timeout + quorum release.
# ---------------------------------------------------------------------------

def _timeout_rows(spec: FaultSpec, depth: int) -> jnp.ndarray:
    """Normalize a spec's timeout to a per-PADDED-level (depth,) row: a
    scalar broadcasts, a shorter row is tail-padded with ``+inf``.
    Padding levels are singleton pass-throughs under ANY timeout
    (``min(x, x + t) == x`` for ``t >= 0``), so the alignment only
    matters for the real levels."""
    t = jnp.asarray(spec.timeout_cycles, jnp.float32)
    if t.ndim == 0:
        return jnp.broadcast_to(t, (depth,))
    if t.shape[0] < depth:
        pad = jnp.full((depth - t.shape[0],), jnp.inf, jnp.float32)
        return jnp.concatenate([t, pad])
    return t[:depth]


def _group_rank(gs: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Service rank of each sorted request WITHIN its group.

    ``gs`` is the group-id column co-sorted with the per-bank service
    order, so within a group (one counter = one bank) increasing sorted
    position IS service order.  A stable sort of ``gs`` makes each
    group a contiguous run whose offset from its first occurrence is
    the rank; the co-sorted ``idx`` scatters ranks back to sorted
    positions."""
    g2, pos = jax.lax.sort((gs, idx), num_keys=1)
    is_start = jnp.concatenate([jnp.ones((1,), bool), g2[1:] != g2[:-1]])
    rank = idx - _segment_first(is_start, idx)
    return jnp.zeros_like(idx).at[pos].set(rank)


def _robust_release(start, gs, grank, g, q, tmo, num_segments):
    """Per-counter release algebra shared by both robust cores.

    Within-group service starts are nondecreasing in sorted order, so
    the K-th serviced child's start is the max over the first
    ``k = clip(ceil(q * g), 1, g)`` ranks; the watchdog deadline counts
    from the FIRST serviced child.  Returns per-group-slot
    ``(release, fired)``.  Degeneracy: ``q == 1`` masks nothing
    (``k == g``), ``tmo == +inf`` pushes the deadline to ``+inf``, and
    ``min(quorum_start, +inf)`` is the plain core's group max bit for
    bit."""
    gf = g.astype(jnp.float32)
    k = jnp.clip(jnp.ceil(q * gf), 1.0, gf)
    in_quorum = grank.astype(jnp.float32) < k
    qstart = jax.ops.segment_max(
        jnp.where(in_quorum, start, -jnp.inf), gs,
        num_segments=num_segments)
    fstart = -jax.ops.segment_max(-start, gs, num_segments=num_segments)
    deadline = fstart + tmo
    return jnp.minimum(qstart, deadline), deadline < qstart


def _robust_result(arrivals, ready, ok, cfg, n):
    """Final reductions shared by both robust cores: stats over the
    SURVIVING PEs.  Every op is a bitwise identity when nothing failed
    (``where`` with an all-true mask, ``max`` over the unmasked
    arrivals, ``mean * n/n``)."""
    exit_time = ready[0] + cfg.wakeup_cycles
    live0 = jnp.isfinite(arrivals)
    last_arrival = jnp.max(jnp.where(live0, arrivals, -jnp.inf), axis=-1)
    n_ok = jnp.sum(ok)
    abandoned = jnp.int32(n) - n_ok
    resid = pe_mean(jnp.where(ok, exit_time[..., None] - arrivals, 0.0))
    mean_res = resid * (jnp.float32(n)
                        / jnp.maximum(n_ok, 1).astype(jnp.float32))
    return exit_time, last_arrival, mean_res, abandoned


def _scan_robust_core(arrivals: jnp.ndarray, table: LevelTable,
                      cfg: TeraPoolConfig, widths: tuple | None = None,
                      spec: FaultSpec = None) -> BarrierResult:
    """:func:`_scan_core` with timeout/quorum release and per-PE
    completion tracking (see the module docstring's fault model).

    The level walk is identical until the counter releases: instead of
    waiting for its last child, each counter releases at
    ``min(kth_serviced_start, first_serviced_start + timeout)``.
    Children whose service start lies after their counter's release
    are *abandoned*: live lane ``l`` of a level with ``m`` live lanes
    represents the contiguous block of ``n // m`` original PEs (lane
    compaction preserves contiguity level over level), so the block is
    struck from the per-PE ``ok`` vector.  A fully-dead subtree whose
    own counter never released carries ``+inf`` upward and is abandoned
    at whichever ancestor does release.

    All fault knobs (mask-conditioned arrivals, timeout row, quorum
    fraction) are traced data: one compiled program covers every fault
    scenario over one cluster, exactly like the plain core.
    """
    n = arrivals.shape[-1]
    arrivals = jnp.asarray(arrivals, jnp.float32)
    idx = jnp.arange(n)
    width = table.bank_ids.shape[-1]
    depth = table.group_sizes.shape[-1]
    tmo_rows = _timeout_rows(spec, depth)
    q = jnp.asarray(spec.quorum_frac, jnp.float32)

    ready0 = arrivals + table.entry_instr
    ok0 = jnp.isfinite(arrivals)

    def step(carry, level):
        ready, m, ok, timed = carry
        g, lat_col, instr, bank_col, svc, tmo = level
        grp = idx // g
        bank = bank_col[jnp.minimum(grp, width - 1)]
        order = jnp.lexsort((ready, bank))
        a = ready[order]
        b = bank[order]
        gs = grp[order]
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), b[1:] != b[:-1]])
        rank = (idx - _segment_first(is_start, idx)).astype(jnp.float32)
        start = _segmented_cummax(a - rank * svc, is_start) + rank * svc
        grank = _group_rank(gs, idx)
        release, fired = _robust_release(start, gs, grank, g, q, tmo, n)
        done = release + lat_col[jnp.minimum(idx, width - 1)]
        # Strike the abandoned children's original-PE blocks.  Phantom
        # groups (all-+inf) never release finitely nor fire, so only
        # live groups contribute.
        ab_lane = jnp.zeros((n,), bool).at[order].set(start > release[gs])
        span = jnp.int32(n) // m
        ok = ok & ~ab_lane[idx // span]
        timed = timed + jnp.any(fired).astype(jnp.int32)
        m = m // g
        ready = jnp.where(idx < m, done + instr, jnp.inf)
        return (ready, m, ok, timed), None

    TRACE_COUNTS["scan_robust_core"] += 1
    levels = (table.group_sizes, table.latencies, table.instr_cycles,
              table.bank_ids, table.service_cycles, tmo_rows)
    (ready, _, ok, timed), _ = jax.lax.scan(
        step, (ready0, jnp.int32(n), ok0, jnp.int32(0)), levels)

    exit_time, last_arrival, mean_res, abandoned = _robust_result(
        arrivals, ready, ok, cfg, n)
    return BarrierResult(
        exit_time=exit_time,
        last_arrival=last_arrival,
        span_cycles=exit_time - last_arrival,
        mean_residency=mean_res,
        energy=robust_episode_energy(
            table.energy_static, table.active_cycles, table.idle_power,
            n, mean_res, spec.e_timeout_poll, timed.astype(jnp.float32),
            spec.e_abandon, abandoned.astype(jnp.float32)),
        completed=jnp.isfinite(exit_time),
        abandoned_pes=abandoned,
        timed_out_levels=timed,
    )


# One TPU lane tile: the narrowest window of a robust telescope step.
_ROBUST_MIN_WIDTH = 128


def _telescope_robust_core(arrivals: jnp.ndarray, table: LevelTable,
                           cfg: TeraPoolConfig,
                           widths: tuple | None = None,
                           spec: FaultSpec = None) -> BarrierResult:
    """:func:`_telescope_core` with timeout/quorum release — the same
    shrinking-width pyramid, the same release algebra as
    :func:`_scan_robust_core` (the two are bit-for-bit equal at every
    width table, like their plain twins).  The only extra per-step work
    is one stable sort for the within-group service rank and the
    abandonment scatter, both confined to the step's window.

    Windows never shrink below ``_ROBUST_MIN_WIDTH`` lanes (any upper
    bound on the live count is sound, see :func:`_telescope_core`): on
    a TPU v5e, the full N=1024 robust grid of 130 schedules put 2 of
    its 3120 episodes (both on the radix-2 tree) one cycle late with
    narrower windows, and matched the scan core with 128-lane ones."""
    n = arrivals.shape[-1]
    arrivals = jnp.asarray(arrivals, jnp.float32)
    width = table.bank_ids.shape[-1]
    depth = table.group_sizes.shape[-1]
    tmo_rows = _timeout_rows(spec, depth)
    q = jnp.asarray(spec.quorum_frac, jnp.float32)

    if widths is None:
        widths = default_widths(n, depth)
    if len(widths) != depth + 1:
        raise ValueError(
            f"widths table has {len(widths)} entries for a depth-"
            f"{depth} table; need depth + 1")

    TRACE_COUNTS["telescope_robust_core"] += 1

    ready = arrivals + table.entry_instr
    ok = jnp.isfinite(arrivals)
    timed = jnp.int32(0)
    idx_n = jnp.arange(n)
    m = jnp.int32(n)
    for i in range(depth):
        w = min(max(int(widths[i]), _ROBUST_MIN_WIDTH), n)
        ready = ready[:w]
        idx = jnp.arange(w)
        g = table.group_sizes[i]
        svc = table.service_cycles[i]
        grp = idx // g
        bank = table.bank_ids[i][jnp.minimum(grp, width - 1)]
        with _phase(i, "sort"):
            b, a, gs, lane = jax.lax.sort((bank, ready, grp, idx),
                                          num_keys=2)
        with _phase(i, "rank"):
            is_start = jnp.concatenate(
                [jnp.ones((1,), bool), b[1:] != b[:-1]])
            rank = (idx - _segment_first(is_start, idx)).astype(
                jnp.float32)
        with _phase(i, "scan"):
            start = (_segmented_cummax(a - rank * svc, is_start)
                     + rank * svc)
        # The within-group rank's own stable sort counts as "rank".
        with _phase(i, "rank"):
            grank = _group_rank(gs, idx)
        with _phase(i, "segmax"):
            release, fired = _robust_release(start, gs, grank, g, q,
                                             tmo_rows[i], w)
            done = release + table.latencies[i][jnp.minimum(idx,
                                                            width - 1)]
        # Compaction here also scatters the abandoned lanes back to PEs.
        with _phase(i, "compact"):
            ab_lane = jnp.zeros((w,), bool).at[lane].set(
                start > release[gs])
            span = jnp.int32(n) // m
            ok = ok & ~ab_lane[idx_n // span]
            timed = timed + jnp.any(fired).astype(jnp.int32)
            m = m // g
            w_next = min(max(int(widths[i + 1]), _ROBUST_MIN_WIDTH), w)
            ready = jnp.where(jnp.arange(w_next) < m,
                              done[:w_next] + table.instr_cycles[i],
                              jnp.inf)

    exit_time, last_arrival, mean_res, abandoned = _robust_result(
        arrivals, ready, ok, cfg, n)
    return BarrierResult(
        exit_time=exit_time,
        last_arrival=last_arrival,
        span_cycles=exit_time - last_arrival,
        mean_residency=mean_res,
        energy=robust_episode_energy(
            table.energy_static, table.active_cycles, table.idle_power,
            n, mean_res, spec.e_timeout_poll, timed.astype(jnp.float32),
            spec.e_abandon, abandoned.astype(jnp.float32)),
        completed=jnp.isfinite(exit_time),
        abandoned_pes=abandoned,
        timed_out_levels=timed,
    )


_CORE_FNS = {"scan": _scan_core, "telescope": _telescope_core}
_ROBUST_CORE_FNS = {"scan": _scan_robust_core,
                    "telescope": _telescope_robust_core}


def resolve_core(core: str | None = None) -> str:
    """Normalize a core selector (``"telescope"`` | ``"scan"`` |
    ``None`` for the session default) to a validated core name — the
    static-argument form every jitted entry point shares."""
    name = DEFAULT_CORE if core is None else core
    if name not in _CORE_FNS:
        raise ValueError(
            f"unknown simulator core {name!r}; choose from {CORES}")
    return name


def core_fn(core: str | None = None, *, robust: bool = False):
    """Resolve a core selector to its implementation (``robust=True``
    for the timeout/quorum fault-model variant)."""
    name = resolve_core(core)
    return _ROBUST_CORE_FNS[name] if robust else _CORE_FNS[name]


@partial(jax.jit, static_argnums=(2, 3, 4), donate_argnums=(0,))
def _simulate_flat(arrivals: jnp.ndarray, table: LevelTable,
                   cfg: TeraPoolConfig, core: str,
                   widths: tuple | None) -> BarrierResult:
    """Jitted (trials, n_pes) batch of the selected core.  The arrival
    block is donated: it is a flattened copy owned by
    :func:`simulate_table`, so its buffer can be reused in place on
    backends that support donation.  ``widths`` is the static
    telescope width table (``None`` = the conservative default)."""
    fn = core_fn(core)
    return jax.vmap(lambda a: fn(a, table, cfg, widths))(arrivals)


@partial(jax.jit, static_argnums=(2, 3, 4), donate_argnums=(0,))
def _simulate_flat_robust(arrivals: jnp.ndarray, table: LevelTable,
                          cfg: TeraPoolConfig, core: str,
                          widths: tuple | None,
                          spec: FaultSpec) -> BarrierResult:
    """Robust twin of :func:`_simulate_flat`.  The spec rides as a
    traced pytree argument: new timeouts / quorums / fault masks reuse
    the one compiled program."""
    fn = core_fn(core, robust=True)
    return jax.vmap(lambda a: fn(a, table, cfg, widths, spec))(arrivals)


def simulate_table(arrivals: jnp.ndarray, table: LevelTable,
                   cfg: TeraPoolConfig = DEFAULT, *,
                   core: str | None = None,
                   faults: FaultSpec | None = None,
                   fault_mask=None) -> BarrierResult:
    """Simulate directly from a padded :class:`LevelTable`.

    Accepts any leading batch shape on ``arrivals``; all batch entries
    run through one jitted, vmapped program.  ``core`` selects the
    simulator implementation (default :data:`DEFAULT_CORE`).

    ``faults`` switches to the degradation-tolerant cores
    (timeout/quorum release, see the module docstring);
    ``fault_mask`` fail-stops the masked PEs by setting their arrivals
    to ``+inf`` (any shape broadcastable against ``arrivals``).  Both
    are traced data — the fault path has its own single compiled
    program per (shape, core, widths).
    """
    if fault_mask is not None and faults is None:
        faults = fault_spec()
    # Light check (group-size column only): tables from level_table /
    # stack_tables were fully validated at construction; this guards
    # hand-built tables without a per-call host sync of the big
    # latency columns.
    table = validate_tail_padding(table, full=False)
    arrivals = jnp.asarray(arrivals, jnp.float32)
    if fault_mask is not None:
        arrivals = jnp.where(jnp.asarray(fault_mask, bool), jnp.inf,
                             arrivals)
    batch = arrivals.shape[:-1]
    widths = telescope_widths(table, arrivals.shape[-1])
    # jnp.copy guarantees _simulate_flat donates a private buffer, never
    # the caller's array (asarray/reshape can alias their input).
    flat = jnp.copy(arrivals.reshape((-1, arrivals.shape[-1])))
    with quiet_donation():
        if faults is None:
            res = _simulate_flat(flat, table, cfg, resolve_core(core),
                                 widths)
        else:
            res = _simulate_flat_robust(flat, table, cfg,
                                        resolve_core(core), widths, faults)
    return BarrierResult(*(x.reshape(batch) for x in res))


def simulate(arrivals: jnp.ndarray, schedule: BarrierSchedule,
             cfg: TeraPoolConfig = DEFAULT, *,
             placement=None, core: str | None = None,
             energy_model: EnergyModel = DEFAULT_ENERGY,
             faults: FaultSpec | None = None,
             fault_mask=None) -> BarrierResult:
    """Simulate one barrier episode (or a leading batch of them).

    Args:
      arrivals: (..., n_pes) per-PE barrier-entry cycles (float or int).
      schedule: static tree structure from :mod:`repro.core.barrier`.
      cfg: machine model.
      placement: optional :class:`~repro.core.placement.CounterPlacement`
        mapping every counter to a concrete bank; ``None`` uses the
        legacy span-heuristic latencies with conflict-free banks.
      core: simulator implementation, ``"telescope"`` (default) or
        ``"scan"`` (the bit-for-bit oracle core).
      energy_model: per-event cost model pricing the ``energy`` column
        (:mod:`repro.core.energy`).
      faults: optional :class:`~repro.core.barrier.FaultSpec` enabling
        timeout/quorum release semantics (the degradation-tolerant
        cores).
      fault_mask: optional per-PE bool mask (broadcastable against
        ``arrivals``); masked PEs fail-stop (arrival ``+inf``).

    Returns:
      :class:`BarrierResult` with the leading batch shape of ``arrivals``.
    """
    arrivals = jnp.asarray(arrivals, jnp.float32)
    if arrivals.shape[-1] != schedule.n_pes:
        raise ValueError(
            f"arrivals has {arrivals.shape[-1]} PEs, schedule expects "
            f"{schedule.n_pes}")
    table = level_table(schedule, cfg=cfg, placement=placement,
                        energy_model=energy_model)
    return simulate_table(arrivals, table, cfg, core=core, faults=faults,
                          fault_mask=fault_mask)


def simulate_reference(arrivals: jnp.ndarray, schedule: BarrierSchedule,
                       cfg: TeraPoolConfig = DEFAULT,
                       energy_model: EnergyModel = DEFAULT_ENERGY
                       ) -> BarrierResult:
    """The seed per-level Python loop, kept as the equivalence oracle.

    Retraces per schedule (shape-changing reshapes); use only in tests
    and spot checks.
    """
    arrivals = jnp.asarray(arrivals, jnp.float32)
    if arrivals.shape[-1] != schedule.n_pes:
        raise ValueError(
            f"arrivals has {arrivals.shape[-1]} PEs, schedule expects "
            f"{schedule.n_pes}")

    # The hardware event unit replaces the software level path: one
    # trigger store on entry, parallel (unserialized) stage
    # aggregation, zero per-level bookkeeping.
    hw = schedule.hw
    entry = cfg.hw_entry_instr if hw else cfg.instr_per_level
    instr = 0 if hw else cfg.instr_per_level
    svc = 0 if hw else None

    # Ready time of the survivors entering the current level.  Level 0:
    # every PE, offset by the per-level software path (call, address
    # computation, atomic issue).
    ready = arrivals + entry
    for lvl in schedule.levels:
        grouped = ready.reshape(ready.shape[:-1] + (-1, lvl.group_size))
        done = _serialize_group(grouped, lvl.latency, cfg, svc=svc)
        # Survivors run the compare/branch + counter-reset + next-level
        # setup before issuing the next atomic.
        ready = done + instr

    # ``ready`` is now (..., 1): the final survivor after its bookkeeping.
    final = ready[..., 0]
    exit_time = final + cfg.wakeup_cycles
    last_arrival = jnp.max(arrivals, axis=-1)
    mean_res = pe_mean(exit_time[..., None] - arrivals)
    stat, act, idle = schedule_energy_constants(
        schedule, None, cfg, energy_model)
    zeros = jnp.zeros(exit_time.shape, jnp.int32)
    return BarrierResult(
        exit_time=exit_time,
        last_arrival=last_arrival,
        span_cycles=exit_time - last_arrival,
        mean_residency=mean_res,
        energy=episode_energy(jnp.float32(stat), jnp.float32(act),
                              jnp.float32(idle), schedule.n_pes, mean_res),
        completed=jnp.isfinite(exit_time),
        abandoned_pes=zeros,
        timed_out_levels=zeros,
    )


# ---------------------------------------------------------------------------
# Independent numpy fault oracle (test-only).
# ---------------------------------------------------------------------------

def _oracle_rows(schedule: BarrierSchedule, placement) -> list:
    """Per level: ``(group_size, bank ids per counter, latency per
    counter)`` — derived straight from the schedule/placement, not from
    any LevelTable, so the oracle shares no table-building code with
    the cores.  Without a placement every counter gets a distinct bank
    (the conflict-free default) at its level's span-heuristic
    latency."""
    rows = []
    m = schedule.n_pes
    for li, lvl in enumerate(schedule.levels):
        count = m // lvl.group_size
        if placement is not None:
            banks = np.asarray(placement.banks[li][:count], np.int64)
            lats = np.asarray(placement.latencies[li][:count], np.float32)
        else:
            banks = np.arange(count, dtype=np.int64)
            lats = np.full(count, np.float32(lvl.latency), np.float32)
        rows.append((lvl.group_size, banks, lats))
        m = count
    return rows


def _robust_episode(arr: np.ndarray, rows: list, cfg: TeraPoolConfig,
                    hw: bool, timeout_row: np.ndarray, q: float) -> tuple:
    """One degradation-tolerant episode as an explicit numpy walk:
    per-bank FIFO queues served at the bank interval, per-counter
    quorum/timeout release, per-PE abandonment bookkeeping.  Float32
    op-for-op the sequence of the robust cores, but organized as
    per-bank/per-counter loops rather than segmented scans."""
    f32 = np.float32
    n = arr.size
    entry = f32(cfg.hw_entry_instr if hw else cfg.instr_per_level)
    svc = f32(0.0 if hw else cfg.bank_service_cycles)
    instr = f32(0.0 if hw else cfg.instr_per_level)
    ready = arr.astype(f32) + entry
    ok = np.isfinite(arr)
    timed = 0
    m = n
    for li, (g, banks, lats) in enumerate(rows):
        tmo = f32(timeout_row[li])
        n_grp = m // g
        grp = np.arange(m) // g
        bank = banks[grp]
        order = np.lexsort((ready, bank))   # stable: (bank, ready, index)
        a = ready[order]
        b = bank[order]
        gs = grp[order]
        # Per-bank FIFO: within a bank run, max-plus service starts.
        start = np.empty(m, f32)
        pos = 0
        while pos < m:
            end = pos
            while end < m and b[end] == b[pos]:
                end += 1
            r = np.arange(end - pos, dtype=f32) * svc
            start[pos:end] = np.maximum.accumulate(a[pos:end] - r) + r
            pos = end
        # K-of-g quorum: ceil in f32 exactly as the cores compute it.
        k = int(min(max(float(np.ceil(f32(q) * f32(g))), 1.0), float(g)))
        done = np.empty(n_grp, f32)
        ab_lane = np.zeros(m, bool)
        level_fired = False
        for j in range(n_grp):
            sel = np.where(gs == j)[0]      # increasing = service order
            s_g = start[sel]
            qstart = f32(np.max(s_g[:k]))
            fstart = f32(np.min(s_g))
            deadline = f32(fstart + tmo)
            release = min(qstart, deadline)
            if deadline < qstart:
                level_fired = True
            done[j] = f32(release + f32(lats[j]))
            ab_lane[order[sel[s_g > release]]] = True
        span = n // m
        for lane in np.nonzero(ab_lane)[0]:
            ok[lane * span:(lane + 1) * span] = False
        timed += int(level_fired)
        ready = done + instr
        m = n_grp
    exit_time = f32(ready[0] + f32(cfg.wakeup_cycles))
    return exit_time, ok, timed


def simulate_robust_reference(arrivals, schedule: BarrierSchedule,
                              cfg: TeraPoolConfig = DEFAULT, *,
                              placement=None,
                              faults: FaultSpec | None = None,
                              fault_mask=None,
                              energy_model: EnergyModel = DEFAULT_ENERGY
                              ) -> BarrierResult:
    """Independent numpy oracle for the degradation-tolerant cores:
    explicit per-bank queues, per-counter quorum/timeout release and
    per-PE abandonment, for one episode or a leading batch.  The final
    reductions mirror the cores' jnp ops (same values in, same float32
    ops out) and the energy rides the shared jitted
    :func:`repro.core.energy.robust_episode_energy`, so agreement is
    bit-for-bit.  Pure python loops — test-only."""
    if faults is None:
        faults = fault_spec()
    arr = np.asarray(arrivals, np.float32)
    if arr.shape[-1] != schedule.n_pes:
        raise ValueError(
            f"arrivals has {arr.shape[-1]} PEs, schedule expects "
            f"{schedule.n_pes}")
    if fault_mask is not None:
        arr = np.where(np.broadcast_to(np.asarray(fault_mask, bool),
                                       arr.shape), np.float32(np.inf), arr)
    n = schedule.n_pes
    batch = arr.shape[:-1]
    flat = arr.reshape((-1, n))

    hw = bool(getattr(schedule, "hw", False))
    if hw and placement is not None:
        raise ValueError(
            "hardware event-unit barriers have no counters to place")
    rows = _oracle_rows(schedule, placement)
    t = np.asarray(faults.timeout_cycles, np.float32)
    depth = len(schedule.levels)
    if t.ndim == 0:
        timeout_row = np.full(depth, t, np.float32)
    else:
        timeout_row = np.full(depth, np.inf, np.float32)
        timeout_row[:min(depth, t.shape[0])] = t[:depth]
    q = float(np.float32(faults.quorum_frac))

    walks = [_robust_episode(a, rows, cfg, hw, timeout_row, q)
             for a in flat]
    exits = jnp.asarray(np.asarray([w[0] for w in walks], np.float32))
    oks = jnp.asarray(np.stack([w[1] for w in walks]))
    timed = jnp.asarray(np.asarray([w[2] for w in walks], np.int32))

    arr_j = jnp.asarray(flat)
    live0 = jnp.isfinite(arr_j)
    last = jnp.max(jnp.where(live0, arr_j, -jnp.inf), axis=-1)
    n_ok = jnp.sum(oks, axis=-1)
    abandoned = jnp.int32(n) - n_ok
    resid = pe_mean(jnp.where(oks, exits[:, None] - arr_j, 0.0))
    mean_res = resid * (jnp.float32(n)
                        / jnp.maximum(n_ok, 1).astype(jnp.float32))
    stat, act, idle = schedule_energy_constants(
        schedule, placement, cfg, energy_model)
    energy = robust_episode_energy(
        jnp.float32(stat), jnp.float32(act), jnp.float32(idle), n,
        mean_res, jnp.asarray(faults.e_timeout_poll, jnp.float32),
        timed.astype(jnp.float32),
        jnp.asarray(faults.e_abandon, jnp.float32),
        abandoned.astype(jnp.float32))
    return BarrierResult(
        exit_time=exits.reshape(batch),
        last_arrival=last.reshape(batch),
        span_cycles=(exits - last).reshape(batch),
        mean_residency=mean_res.reshape(batch),
        energy=jnp.asarray(energy).reshape(batch),
        completed=jnp.isfinite(exits).reshape(batch),
        abandoned_pes=abandoned.reshape(batch),
        timed_out_levels=timed.reshape(batch),
    )


def uniform_arrivals(key: jax.Array, max_delay: float, n_pes: int,
                     n_trials: int = 16) -> jnp.ndarray:
    """The paper's synthetic benchmark (Sec. 4.1): per-PE delay drawn
    uniformly from [0, max_delay]."""
    if max_delay <= 0:
        return jnp.zeros((n_trials, n_pes), jnp.float32)
    return jax.random.uniform(key, (n_trials, n_pes), jnp.float32,
                              0.0, max_delay)


def mean_span_cycles(key: jax.Array, schedule: BarrierSchedule,
                     max_delay: float, cfg: TeraPoolConfig = DEFAULT,
                     n_trials: int = 16) -> jnp.ndarray:
    """Average Fig. 4a metric (last-in -> last-out cycles) over trials."""
    arr = uniform_arrivals(key, max_delay, schedule.n_pes, n_trials)
    return jnp.mean(simulate(arr, schedule, cfg).span_cycles)


def overhead_fraction(key: jax.Array, schedule: BarrierSchedule,
                      sfr_cycles: float, max_delay: float,
                      cfg: TeraPoolConfig = DEFAULT,
                      n_trials: int = 16) -> jnp.ndarray:
    """Fig. 4b metric: mean per-PE barrier residency over total runtime,
    as a function of the synchronization-free region (SFR)."""
    arr = uniform_arrivals(key, max_delay, schedule.n_pes, n_trials)
    res = simulate(arr, schedule, cfg)
    barrier = jnp.mean(res.mean_residency)
    return barrier / (sfr_cycles + barrier)
