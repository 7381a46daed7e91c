"""The paper's 5G application end to end, twice:

1. *Simulated on TeraPool* — the cycle-level model reproducing Fig. 7
   (central vs tree vs partial barriers).
2. *Executed on the TPU kernel stack* — the radix-4 FFT stage kernels +
   beamforming matmul from repro.kernels actually process an OFDM
   batch (interpret mode on CPU), validated against numpy.

    PYTHONPATH=src python examples/fiveg_pipeline.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import fiveg
from repro.kernels import ops, ref


def simulate():
    print("== TeraPool simulation (Fig. 7) ==")
    key = jax.random.PRNGKey(0)
    for n_rx in (16, 32, 64):
        app = fiveg.FiveGConfig(n_rx=n_rx, ffts_per_round=4)
        res = fiveg.compare_barriers(key, app, radix=32)
        print(f" N_RX={n_rx:3d}: central={float(res['central'].total_cycles):9.0f}cy"
              f"  partial32={float(res['partial'].total_cycles):9.0f}cy"
              f"  speedup={float(res['speedup_partial']):.2f}x"
              f"  sync={float(res['partial'].sync_fraction) * 100:.1f}%")


def execute() -> dict:
    """Run the kernels on an OFDM batch; returns their largest
    absolute errors against numpy (asserting the tolerances)."""
    print("\n== TPU kernel pipeline (OFDM demod + beamforming) ==")
    rng = np.random.default_rng(0)
    n_rx, n_sc, n_beams = 8, 1024, 4
    # antenna streams (time domain)
    re = jnp.asarray(rng.standard_normal((n_rx, n_sc)), jnp.float32)
    im = jnp.asarray(rng.standard_normal((n_rx, n_sc)), jnp.float32)

    # OFDM demodulation: radix-4 DIF FFT per antenna (pallas stages)
    fr, fi = ops.fft4(re, im)
    idx = np.asarray(ref.digit_reverse_indices(n_sc))
    want = np.fft.fft(np.asarray(re) + 1j * np.asarray(im), axis=-1)
    np.testing.assert_allclose(np.asarray(fr)[:, idx], want.real,
                               rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(fi)[:, idx], want.imag,
                               rtol=1e-3, atol=2e-3)
    fft_err = float(np.max(np.abs(np.asarray(fr)[:, idx] - want.real)))
    print(f" FFT: {n_rx} x {n_sc}-pt radix-4 OK (max err {fft_err:.2e})")

    # beamforming: (n_beams x n_rx) @ (n_rx x n_sc), pallas matmul
    coef = jnp.asarray(rng.standard_normal((n_beams, n_rx)), jnp.float32)
    beams_r = ops.matmul(coef, fr)
    beams_i = ops.matmul(coef, fi)
    want_r = np.asarray(coef) @ np.asarray(fr)
    np.testing.assert_allclose(beams_r, want_r, rtol=1e-4, atol=1e-3)
    beam_err = float(np.max(np.abs(np.asarray(beams_r) - want_r)))
    print(f" beamforming: {n_beams} beams x {n_sc} subcarriers OK "
          f"(max err {beam_err:.2e})")
    print(" output power per beam:",
          np.round(np.mean(np.asarray(beams_r) ** 2
                           + np.asarray(beams_i) ** 2, axis=1), 1))
    return {"fft_max_err": fft_err, "matmul_max_err": beam_err}


if __name__ == "__main__":
    compile_cache.enable()
    simulate()
    execute()
