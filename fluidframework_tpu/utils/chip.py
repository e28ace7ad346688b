"""What a process that holds the accelerator does first.

The chip belongs to one process at a time, and JAX carries on on the CPU
when no TPU backend comes up. So every entry point meant to hold the
chip — the applier stage, ``chip_smoke.py`` and
``tools/profile_applier.py`` — calls :func:`require_tpu` and
:func:`init_compile_cache` at the start of its ``main()``. Nothing calls
them at import time, and tests never do: a CPU run asks for the CPU with
``JAX_PLATFORMS=cpu`` at launch.
"""

from __future__ import annotations

import os

#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed path inside the checkout (the path is part of the cache key,
#: so a directory that moves between runs never hits); .gitignore'd
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: the jax.monitoring event that times each backend compile — or, on a
#: persistent-cache hit, the load that replaced it
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require_tpu():
    """First device of the live backend; raises unless it is a TPU or
    the CPU was asked for (``JAX_PLATFORMS=cpu``)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and jax.config.jax_platforms != "cpu":
        raise RuntimeError(
            f"no TPU: JAX came up on {dev.platform} ({dev.device_kind}); "
            "set JAX_PLATFORMS=cpu to run on the CPU on purpose")
    return dev


def compile_cache_dir() -> str:
    """The compile cache directory a chip-holding process uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def init_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory, before
    the process's first compile, and return the directory in use. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return compile_cache_dir()
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def peak_bytes_in_use(device):
    """``peak_bytes_in_use`` where the backend reports it, else None."""
    stats = device.memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


class CompileClock:
    """Seconds this process spent compiling (cache loads included) since
    the clock was made."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1
