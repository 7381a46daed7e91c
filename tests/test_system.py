"""End-to-end behaviour tests for the whole system."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint, configs, optim
from repro.data import DataConfig, batch_for_model
from repro.models import init_params, loss_fn
from repro.runtime import FaultConfig, FaultTolerantRunner

KEY = jax.random.PRNGKey(0)
REPO = Path(__file__).resolve().parent.parent


def test_end_to_end_training_reduces_loss(tmp_path):
    """Train a small qwen3-family model for 60 steps through the
    fault-tolerant runner: loss must drop substantially; a restart from
    checkpoint must continue, not regress."""
    cfg = configs.get_smoke("qwen3_4b")
    dcfg = DataConfig(seed=1, seq_len=64, global_batch=8,
                      vocab_size=cfg.vocab_size)
    ocfg = optim.OptConfig.from_model(cfg, lr=3e-3, warmup_steps=10,
                                      total_steps=120, weight_decay=0.01)
    params = init_params(cfg, KEY)
    opt_state = optim.init(params, ocfg)

    @jax.jit
    def train_step(state, batch):
        p, s = state
        (loss, metrics), grads = jax.value_and_grad(
            lambda q: loss_fn(q, cfg, batch), has_aux=True)(p)
        p2, s2 = optim.update(grads, s, p, ocfg)
        return (p2, s2), metrics

    def batch_fn(step):
        return jax.tree.map(jnp.asarray, batch_for_model(cfg, dcfg, step))

    losses = []
    runner = FaultTolerantRunner(
        FaultConfig(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=25),
        step_fn=train_step, batch_fn=batch_fn,
        state_template=(params, opt_state))
    runner.run(60, on_step=lambda s: losses.append(s.metrics["loss"]))

    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < first - 0.5, (first, last)

    # restart resumes from the checkpoint, loss stays near the tail
    runner2 = FaultTolerantRunner(
        FaultConfig(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=25),
        step_fn=train_step, batch_fn=batch_fn,
        state_template=(params, opt_state))
    assert runner2.resume_step() == 60   # saved at 24, 49 and 59
    more = []
    runner2.run(65, on_step=lambda s: more.append(s.metrics["loss"]))
    assert np.mean(more) < first - 0.4


def test_distribution_suite_multidevice():
    """Re-run the sharded-step tests on an 8-device host platform."""
    env = dict(os.environ)
    env["REPRO_MULTIDEV"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x",
         str(REPO / "tests" / "test_distribution.py")],
        env=env, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]


def test_dryrun_cell_cli(tmp_path):
    """The dry-run CLI lowers+compiles one real cell on the production
    512-device multi-pod mesh (the MINIMUM multi-pod requirement)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3-4b",
         "--shape", "decode_32k", "--mesh", "multi", "--out",
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert "ok" in r.stdout
