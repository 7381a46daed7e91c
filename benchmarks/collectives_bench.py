"""Layer-B mapping benchmark: flat (central-counter) vs hierarchical
(tree) vs radix-factored gradient synchronization, counted as the
collective bytes in the HLO lowered for an 8-device mesh.

The count comes from a lowering on 8 forced HOST devices, in a child
process pinned to ``JAX_PLATFORMS=cpu`` (jax locks the device count at
first init, and the parent may already hold the chip).  It is a count
of bytes in the program, not a measured transfer: the rows say
``lowered``.  A failed child fails the benchmark run."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import collectives
from repro.launch import hlo_analysis

out = {}
G = 1 << 20  # 1 Mi-element f32 gradient

def wire(fn, mesh, in_spec, axis_names):
    g = collectives.shard_map_compat(fn, mesh, in_spec, in_spec, axis_names)
    x = jnp.ones((G,), jnp.float32)
    hlo = jax.jit(g).lower(x).compile().as_text()
    return hlo_analysis.analyze(hlo).collective_bytes

mesh2 = jax.make_mesh((2, 4), ("pod", "data"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
out["flat"] = wire(lambda x: collectives.psum_chain(x, ("data", "pod")),
                   mesh2, P(), {"pod", "data"})
out["hier"] = wire(
    lambda x: collectives.gather_param(
        jax.lax.psum(jax.lax.psum_scatter(x, "data", scatter_dimension=0,
                                          tiled=True), "pod"),
        ("data",), 0),
    mesh2, P(), {"pod", "data"})
meshr = collectives.make_factored_mesh(2, model=1, data=4, multi_pod=True)
out["radix2"] = wire(
    lambda x: collectives.tree_psum(x, ("pod", "data0", "data1")),
    meshr, P(), {"pod", "data0", "data1"})
print(json.dumps(out))
"""


def run():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    t0 = time.perf_counter()
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    us = (time.perf_counter() - t0) * 1e6
    if r.returncode != 0:
        raise RuntimeError(
            f"collectives lowering child failed (rc {r.returncode}):\n"
            f"{r.stderr[-2000:]}")
    data = json.loads(r.stdout.strip().splitlines()[-1])
    rows = [(f"collectives_sync_{k}_lowered_wireMiB", us,
             round(v / 2 ** 20, 2)) for k, v in data.items()]
    if data.get("flat"):
        rows.append(("collectives_hier_vs_flat_lowered_ratio", us,
                     round(data["hier"] / data["flat"], 3)))
    return rows
