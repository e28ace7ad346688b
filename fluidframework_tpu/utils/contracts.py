"""Kernel contracts: declarative TPU hot-path invariants, checked statically.

The load-bearing performance claims of ARCHITECTURE.md ("apply is
gather-free", "everything under one jit", "staged ops ship as int16
packed waves") used to live only in prose — a regression in any of them
was silent until a bench run on real hardware. This module turns each
claim into a REGISTERED contract that ``tools/fluidlint`` (the repo's
static contract checker) abstract-evals and enforces in CI, the same way
the reference enforces its layer DAG mechanically with layer-check.

A contract names a hot-path entry point, an example-shape builder (lazy,
so registration costs nothing at import), and the invariants its jaxpr
must satisfy:

- ``no_gather`` / ``no_scatter`` — the traced program (walked through
  every nested jaxpr: scan/while/cond bodies, pjit calls, pallas_call
  kernels) contains no ``gather``/``scatter*`` primitive. Computed-index
  gathers/scatters are the TPU slow path — measured ~6x the entire
  apply for one 64k-row scatter.
- ``max_gathers`` — a budget instead of a ban, for kernels that fuse a
  deliberate once-per-wave gather (zamboni compaction's argsort repack)
  onto the gather-free per-op path. The budget catches a NEW gather
  creeping into the K-amplified part.
- ``max_scatters`` — the same budget for ``scatter*`` primitives, for
  kernels that write a gathered tile of rows back once per wave.
- ``max_dynamic_slices`` — budget for ``dynamic_slice`` equations, the
  second computed-index shape XLA can sink to the slow path.
- ``no_int16_arithmetic`` — no arithmetic primitive consumes an int16
  operand: every packed-wave field must be explicitly widened
  (``astype(int32)``) before math, never silently promoted.
- ``single_jit`` — calling the (jitted) kernel twice with same-shape
  inputs compiles exactly once; catches recompile regressions from
  unhashable statics, weak-type churn, or accidental python-level
  closure rebuilding.

Registration is zero-overhead on the hot path: the decorator records the
function in a module-level registry and returns it UNCHANGED.

This module sits in the bottom layer (``utils``) so every kernel layer
may import it; it imports nothing from the framework.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

#: () -> (args, kwargs) for one example trace of the kernel.
ExampleBuilder = Callable[[], tuple]

#: () -> (fn, example_builder); lets factory-produced kernels (jitted
#: closures keyed by geometry) defer construction to check time.
ContractBuilder = Callable[[], tuple]


@dataclass(frozen=True)
class KernelContract:
    """One registered hot-path entry point and its jaxpr invariants."""

    name: str
    build: ContractBuilder
    no_gather: bool = False
    no_scatter: bool = False
    max_gathers: Optional[int] = None
    max_scatters: Optional[int] = None
    max_dynamic_slices: Optional[int] = None
    no_int16_arithmetic: bool = False
    single_jit: bool = False
    notes: str = ""


# name -> KernelContract; fluidlint imports the kernel modules, which
# populate this at import time
_REGISTRY: dict[str, KernelContract] = {}


def kernel_contract(
    name: str,
    *,
    example: ExampleBuilder,
    no_gather: bool = False,
    no_scatter: bool = False,
    max_gathers: Optional[int] = None,
    max_scatters: Optional[int] = None,
    max_dynamic_slices: Optional[int] = None,
    no_int16_arithmetic: bool = False,
    single_jit: bool = False,
    notes: str = "",
    registry: Optional[dict] = None,
) -> Callable:
    """Decorator form: register ``fn`` under ``name`` and return it
    unchanged. ``example()`` must return ``(args, kwargs)`` the kernel
    can be traced (and, for ``single_jit``, executed) with."""

    def deco(fn: Callable) -> Callable:
        register_kernel_contract(
            name,
            build=lambda: (fn, example),
            no_gather=no_gather,
            no_scatter=no_scatter,
            max_gathers=max_gathers,
            max_scatters=max_scatters,
            max_dynamic_slices=max_dynamic_slices,
            no_int16_arithmetic=no_int16_arithmetic,
            single_jit=single_jit,
            notes=notes,
            registry=registry,
        )
        return fn

    return deco


def register_kernel_contract(
    name: str,
    *,
    build: ContractBuilder,
    registry: Optional[dict] = None,
    **invariants: Any,
) -> KernelContract:
    """Non-decorator form for kernels produced by factories: ``build()``
    returns ``(fn, example_builder)``. Re-registration under the same
    name replaces (idempotent module reloads)."""
    contract = KernelContract(name=name, build=build, **invariants)
    (_REGISTRY if registry is None else registry)[name] = contract
    return contract


def registered_contracts() -> dict[str, KernelContract]:
    """The global registry (populated by importing the kernel modules)."""
    return dict(_REGISTRY)
