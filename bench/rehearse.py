"""Compile every cell's grid programs for a described TPU v5e, without
a chip, and print the compile seconds and ``memory_analysis()``.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

Nothing runs: this finds what the chip's compiler would refuse (a
program that does not fit, a shape it cannot lower) before chip time is
spent.  The shapes are the ones the cell's window dispatches: the whole
tuner grid of a ``tune_barrier`` cell, the arrival grid of a
``sweep_arrivals`` cell, and one arrival grid per fused batch size
(1..max_batch) of a ``serve`` cell.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.lib import common  # noqa: E402
from bench import run as bench_run  # noqa: E402


def _shapes(sharding, tree):
    import jax
    import jax.numpy as jnp
    import numpy as np
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def programs(cell: dict):
    """(label, jitted grid, args) of every grid shape the cell uses."""
    import jax.numpy as jnp
    common.import_program()
    from repro.core import barrier, sweep, tuning
    from repro.core.barrier import fault_spec
    from repro.core.topology import TeraPoolConfig
    traffic = cell["traffic"]
    cfg = TeraPoolConfig(**cell["config"]["machine"])
    n = cfg.n_pes
    if traffic["driver"] == "grid" and traffic["entry"] == "tune_barrier":
        scheds, placs = tuning._cross_placements(
            tuning.all_schedules(n, cfg, prune=traffic["prune"]),
            traffic["placements"], cfg)
        tables = barrier.stack_tables(scheds, cfg, placs)
        delays = jnp.zeros((len(traffic["delays"]),), jnp.float32)
        unit = jnp.zeros((traffic["n_trials"], n), jnp.float32)
        yield ("tune grid", sweep._sweep_grid,
               (tables, delays, unit, cfg, "telescope",
                barrier.telescope_widths(tables, n)))
    elif traffic["driver"] == "grid":
        scheds = tuning.all_schedules(n, cfg, prune=traffic["prune"])
        names = {s.name for s in scheds}
        extra = [barrier.kary_tree(min(r, n), cfg=cfg)
                 for r in traffic.get("extra_radices", ())]
        if traffic.get("central"):
            extra.append(barrier.central_counter(cfg=cfg))
        scheds += [s for s in extra if s.name not in names]
        tables = barrier.stack_tables(scheds, cfg)
        arr = jnp.zeros((len(traffic["kernels"]), traffic["n_trials"], n),
                        jnp.float32)
        spec = fault_spec(timeout_cycles=float(traffic["timeout_cycles"]),
                          quorum_frac=float(traffic["quorum_frac"]))
        yield ("robust arrival grid", sweep._arrival_grid_robust,
               (tables, spec, arr, cfg, "telescope",
                barrier.telescope_widths(tables, n)))
    else:
        prune = traffic.get("prune") or ("none" if n <= 256 else "hierarchy")
        scheds, placs = tuning._cross_placements(
            tuning.all_schedules(n, cfg, prune=prune),
            traffic["placements"], cfg)
        tables = barrier.stack_tables(scheds, cfg, placs)
        for k in range(1, traffic["server"]["max_batch"] + 1):
            arr = jnp.zeros((k, traffic["n_trials"], n), jnp.float32)
            yield (f"serve arrival grid K={k}", sweep._arrival_grid,
                   (tables, jnp.zeros((0,), jnp.float32), arr, cfg,
                    "telescope", barrier.telescope_widths(tables, n)))


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = (argv if argv else [w["name"] for w in spec["workloads"]])
    for name in names:
        cell = bench_run.load_cell(name)
        for label, fn, args in programs(cell):
            arrays, static = args[:3], args[3:]
            t0 = time.perf_counter()
            compiled = fn.lower(*_shapes(chip, arrays), *static).compile()
            dt = time.perf_counter() - t0
            mem = compiled.memory_analysis()
            print(json.dumps({
                "cell": name, "program": label, "compile_s": round(dt, 2),
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
