"""Test harness config.

Smoke/unit tests run on the default single CPU device.  The
distribution tests (tests/test_distribution.py) need several devices;
``tests/test_system.py::test_distribution_suite_multidevice`` re-runs
them in a subprocess with REPRO_MULTIDEV=1, which this conftest turns
into an 8-device host platform BEFORE jax initializes.
"""
import os

if os.environ.get("REPRO_MULTIDEV"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))
