"""The chip smoke script's phases, run small on the CPU.

Every phase of ``chip_smoke.py`` takes the cluster size, so the whole
main path — with its oracle checks — runs here at N=64.  The script's
own ``main`` refuses to run without a TPU; that refusal is tested too.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


@pytest.mark.parametrize("phase", list(smoke.PHASES))
def test_phase_small(phase):
    rec = smoke.PHASES[phase](64)
    assert rec["wall_s"] > 0
    # Every column that was compared matched its oracle exactly.
    assert all(v == 0 for v in rec["max_diff"].values()), rec["max_diff"]


def test_main_refuses_without_tpu(capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_four_chip_phase_on_host_devices():
    """The --four-chips path on four forced host devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + os.environ.get("XLA_FLAGS", ""))
    script = ("import sys; sys.path.insert(0, sys.argv[1]); "
              "import chip_smoke, json; "
              "print(json.dumps(chip_smoke.phase_four_chips(64)))")
    r = subprocess.run([sys.executable, "-c", script, str(REPO)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert '"device_history": [4, 2]' in r.stdout


def test_compile_cache_location(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    from repro import compile_cache
    saved = jax.config.jax_compilation_cache_dir
    try:
        # Set from outside: JAX reads the variable; nothing is overridden.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved
        # Unset: the fixed directory inside the checkout.
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
        compilation_cache.reset_cache()
