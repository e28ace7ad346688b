"""Test configuration: JAX on the CPU with 8 virtual devices, so the
multi-chip sharding paths run without a chip.

Both settings go into the environment before JAX is imported: XLA reads
``XLA_FLAGS`` once, at the first backend init, and the service processes
the tests spawn (applier stages and the like) inherit
``JAX_PLATFORMS=cpu`` — the explicit CPU request those entry points
check for instead of a TPU."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# in case a plugin imported JAX before this file set the environment
jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test, excluded from the tier-1 `-m 'not slow'` "
        "run (multi-seed soaks, network stress)")
    config.addinivalue_line(
        "markers",
        "chaos: exercises the fault-injection plane "
        "(fluidframework_tpu/chaos); `-m chaos` selects the chaos suite")
