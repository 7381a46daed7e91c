"""Pieces every driver shares: seeds, row comparison, compared numbers."""
from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

from . import reference

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_file(path: Path, name: str | None = None):
    """Import one Python file by path (metric and driver files are
    found by name, and their names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def import_program():
    """Put the program under test (``<checkout>/src``) on the path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def seed_key(seed: int, stream: int = 0) -> np.ndarray:
    """A raw ``uint32[2]`` PRNG key from any non-negative integer seed
    (wider than 32 bits too) and a stream number."""
    state = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return np.asarray(state, np.uint32)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(stream)]))


@dataclasses.dataclass
class Check:
    """One number compared against its limit (passes when <= limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def episodes_off(got: Dict[str, np.ndarray],
                 want: Dict[str, np.ndarray]) -> np.ndarray:
    """Boolean mask over episodes: True where any column of the
    program differs from the reference (exact equality; equal
    infinities are equal)."""
    off = None
    for col in reference.COLUMNS:
        g = np.asarray(got[col])
        w = np.asarray(want[col])
        if g.shape != w.shape:
            raise ValueError(f"{col}: shape {g.shape} != {w.shape}")
        if g.dtype.kind == "f" or w.dtype.kind == "f":
            bad = ~((g.astype(np.float64) == w.astype(np.float64))
                    | (np.isnan(g.astype(np.float64))
                       & np.isnan(w.astype(np.float64))))
        else:
            bad = g != w
        off = bad if off is None else (off | bad)
    return off


def max_ulps(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
             ) -> Dict[str, int]:
    """Per float column, the largest distance in float32 ulps (for the
    record; the comparison itself is exact)."""
    def ordered(x):
        i = np.ascontiguousarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    out = {}
    for col in reference.COLUMNS:
        g = np.asarray(got[col])
        if g.dtype.kind != "f" or g.size == 0:
            continue
        out[col] = int(np.max(np.abs(ordered(g) - ordered(want[col]))))
    return out


def label_mismatches(got: Sequence[str], want: Sequence[str]) -> int:
    """Rows whose (schedule, placement) label is not the expected one,
    plus any difference in the number of rows."""
    return (sum(g != w for g, w in zip(got, want))
            + abs(len(got) - len(want)))


def stack_rows(names: List[str], rng_: np.random.Generator,
               n_random: int, n_pes: int) -> List[int]:
    """Rows to compare: for every placement in the stack, the deepest
    tree (all levels of 2), the central counter and ``n_random`` rows
    drawn from the seed.  Unplaced stacks count as one placement."""
    by_strategy: Dict[str | None, List[int]] = {}
    for i, nm in enumerate(names):
        by_strategy.setdefault(reference.parse_name(nm)[1], []).append(i)
    deepest = reference.name_of(reference.kary_sizes(2, n_pes))
    central = reference.name_of((n_pes,))
    rows = set()
    for strategy, idx in by_strategy.items():
        for want in (deepest, central):
            rows.update(i for i in idx
                        if reference.parse_name(names[i])[0]
                        == reference.parse_name(want)[0])
        rest = [i for i in idx if i not in rows]
        k = min(n_random, len(rest))
        rows.update(int(i) for i in rng_.choice(rest, size=k, replace=False))
    return sorted(rows)
