"""Hermetic test backend: JAX CPU platform with 8 virtual devices.

Mirrors the reference's test seam (SURVEY.md §4): the reference tests only
against a real backend over its real protocol; our equivalent hermetic seam is
the in-process JAX CPU backend, with 8 forced host devices so every sharding /
mesh code path is exercised exactly as it would be on a v5e-8 slice.
"""
import os

# Must be set before jax initializes its backends.  The env var covers the
# subprocesses tests spawn; jax.config.update below covers a jax that some
# plugin imported before this file ran.  Tests are hermetic on the CPU
# backend; the chip is reached only through chip_smoke.py and bench.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    import jax

    return jax.devices()
