"""The main path's programs compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a *described* v5e
device, which refuses what the chip's compiler would refuse (Mosaic
layouts, device memory).  Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import barrier, sweep, workloads
from repro.core.barrier import fault_spec
from repro.core.topology import DEFAULT

N = DEFAULT.n_pes
HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Programs compiled for a described chip cannot be read back from
    # the persistent cache without one; keep them out of it.
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved_cache)
    compilation_cache.reset_cache()
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes (with the chip's sharding) of every array leaf of ``tree``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _radix_stack():
    """The 10 uniform radices at N=1024, their tables and widths."""
    scheds = [barrier.kary_tree(r) for r in barrier.all_radices(N)]
    tables = barrier.stack_tables(scheds, DEFAULT)
    return tables, barrier.telescope_widths(tables, N)


def test_sweep_grid_compiles(one_chip):
    tables, widths = _radix_stack()
    delays = jnp.zeros((4,), jnp.float32)
    unit = jnp.zeros((4, N), jnp.float32)
    _fits(sweep._sweep_grid.lower(
        _on(one_chip, tables), _on(one_chip, delays), _on(one_chip, unit),
        DEFAULT, "telescope", widths).compile())


def test_sweep_grid_robust_compiles(one_chip):
    tables, widths = _radix_stack()
    fixed = (jnp.zeros((4,), jnp.float32),
             fault_spec(timeout_cycles=2000.0, quorum_frac=0.95))
    unit = jnp.zeros((4, N), jnp.float32)
    _fits(sweep._sweep_grid_robust.lower(
        _on(one_chip, tables), _on(one_chip, fixed), _on(one_chip, unit),
        DEFAULT, "telescope", widths).compile())


def test_arrival_grid_compiles(one_chip):
    tables, widths = _radix_stack()
    arrivals = jnp.zeros((len(workloads.FIG6_KERNELS[:3]), 4, N),
                         jnp.float32)
    _fits(sweep._arrival_grid.lower(
        _on(one_chip, tables), _on(one_chip, jnp.zeros((0,), jnp.float32)),
        _on(one_chip, arrivals), DEFAULT, "telescope", widths).compile())


@pytest.mark.parametrize("kernel,shapes", [
    ("fft4", ((8, 1024), (8, 1024))),        # the OFDM batch
    ("matmul", ((4, 8), (8, 1024))),         # the beamforming matmul
])
def test_pallas_kernel_compiles(one_chip, monkeypatch, kernel, shapes):
    """The example's kernels compile to Mosaic kernels (interpret mode
    is chosen from the default backend, so pretend it is the chip's)."""
    from repro.kernels import ops
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    # Drop traces cached in either mode, before and after, so neither
    # this compile nor a later CPU call reuses the other's kernel mode.
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        compiled = _fits(getattr(ops, kernel).lower(*args).compile())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
