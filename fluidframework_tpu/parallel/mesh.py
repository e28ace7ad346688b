"""Device mesh construction.

Two named axes:

- ``docs`` — document shards (the Kafka-partition analog; independent docs,
  so this axis only ever carries stats collectives like psum of applied-op
  counts — never data dependencies between docs).
- ``seg``  — segment shards within one giant document (the
  sequence-parallel analog; carries prefix-sum collectives over ICI).

On a real slice the 'docs' axis should span hosts (DCN-tolerant: traffic is
tiny) while 'seg' stays intra-slice (prefix exchanges ride ICI).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(
    n_devices: int | None = None,
    seg_shards: int = 1,
    devices=None,
) -> Mesh:
    """Build a ('docs', 'seg') mesh over ``n_devices`` (default: all)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    n = len(devices)
    if n % seg_shards != 0:
        raise ValueError(f"{n} devices not divisible by seg_shards={seg_shards}")
    grid = np.asarray(devices).reshape(n // seg_shards, seg_shards)
    return Mesh(grid, ("docs", "seg"))


def require_devices(n_devices: int) -> None:
    """At least ``n_devices`` devices. On an explicit CPU run
    (``JAX_PLATFORMS=cpu``: tests, CI, the multichip smoke) host-platform
    virtual devices are forced; otherwise the devices present must do —
    a chip is never swapped for host devices.

    XLA parses ``XLA_FLAGS`` exactly once, at the very first backend
    init, so a CPU run calls this before anything queries devices."""
    import os

    cpu_run = jax.config.jax_platforms == "cpu"
    if cpu_run:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    have = jax.devices()
    if len(have) < n_devices:
        raise RuntimeError(
            f"{n_devices} devices asked for, {len(have)} "
            f"{have[0].platform} devices present"
            + ("; was a backend already initialized with XLA_FLAGS set "
               "differently?" if cpu_run else ""))
