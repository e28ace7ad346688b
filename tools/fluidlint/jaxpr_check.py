"""Pass 2 — jaxpr contracts: the TPU hot-path claims, checked by trace.

Every registered kernel (``fluidframework_tpu.utils.contracts``) is
abstract-evaled on its declared example shapes and its jaxpr walked
recursively — through scan/while/cond bodies, pjit calls, and
``pallas_call`` kernel jaxprs — so a forbidden primitive cannot hide
inside a nested program. Checks:

- forbidden primitives: ``gather`` / ``scatter*`` when the contract bans
  them, budgets (``max_gathers``, ``max_scatters``,
  ``max_dynamic_slices``) otherwise;
- dynamic-index ``while`` bodies: a ``gather``/``dynamic_slice`` inside
  a ``while`` is flagged even under a budget — a computed-index read in
  a device loop body is the K-amplified slow path by construction;
- int16 silent promotion: no arithmetic primitive may consume an int16
  operand (packed-wave fields must be explicitly widened first);
- recompile regressions (``single_jit``): the kernel runs twice with
  same-shape inputs and the pjit compilation-cache size must not grow
  on the second call.

The hot-path kernels named in ``REQUIRED_KERNELS`` must stay
registered — removing a ``@kernel_contract`` registration is itself a
violation, so coverage cannot silently decay.
"""

from __future__ import annotations

import functools
import importlib
import inspect
import os
import warnings
from collections import Counter
from typing import Iterator, Optional

from .report import Violation

#: Modules whose import populates the contract registry.
KERNEL_MODULES = (
    "fluidframework_tpu.ops.apply",
    "fluidframework_tpu.ops.pallas_apply",
    "fluidframework_tpu.parallel.sharded_apply",
    "fluidframework_tpu.service.tpu_applier",
)

#: The hot-path entry points that must stay under contract.
REQUIRED_KERNELS = (
    "ops.apply_ops_batch",
    "ops.pallas_apply_ops_batch",
    "parallel.sharded_step",
    "parallel.sharded_step_packed",
    "parallel.sharded_step_packed_pallas",
    "service.dense_step_packed",
    "service.dense_step_packed_pallas",
    "service.tile_step_packed",
    "service.tile_step_packed_pallas",
)

#: Primitives that do arithmetic (an int16 operand here = silent
#: promotion risk); layout/convert/compare primitives are exempt.
_ARITHMETIC_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "neg", "pow", "integer_pow",
    "max", "min", "dot_general", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "cumsum", "cumprod", "reduce_sum",
    "reduce_prod", "reduce_max", "reduce_min", "abs", "sign",
})


def load_registry() -> dict:
    """Import the kernel modules and return the populated registry."""
    for mod in KERNEL_MODULES:
        importlib.import_module(mod)
    from fluidframework_tpu.utils.contracts import registered_contracts

    return registered_contracts()


def _subjaxprs(params: dict) -> Iterator:
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr"):       # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):      # raw Jaxpr (pallas_call kernel)
                yield x


def walk_eqns(jaxpr, *, in_while: bool = False
              ) -> Iterator[tuple[object, bool]]:
    """Every equation in ``jaxpr`` and its nested jaxprs, tagged with
    whether it sits inside a ``while`` body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_while
        child_in_while = in_while or eqn.primitive.name == "while"
        for sub in _subjaxprs(eqn.params):
            yield from walk_eqns(sub, in_while=child_in_while)


def primitive_counts(jaxpr) -> Counter:
    return Counter(eqn.primitive.name for eqn, _ in walk_eqns(jaxpr))


def _contract_site(fn) -> tuple[str, int]:
    """Best-effort (path, line) for a contract's kernel function."""
    try:
        target = inspect.unwrap(fn)
        target = getattr(target, "__wrapped__", target)
        path = inspect.getsourcefile(target) or "<unknown>"
        _, line = inspect.getsourcelines(target)
        try:
            path = os.path.relpath(path, _repo_root())
        except ValueError:
            pass
        return path, line
    except (TypeError, OSError):
        return "<unknown>", 0


def _trace(fn, args, kwargs):
    import jax

    return jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)


def check_contract(contract) -> list[Violation]:
    """Abstract-eval one registered kernel and enforce its invariants."""
    name = contract.name

    def v(message, path="<registry>", line=0, suggestion=""):
        return Violation(pass_name="jaxpr", path=path, line=line,
                         message=f"kernel '{name}': {message}",
                         suggestion=suggestion)

    try:
        fn, example = contract.build()
    except Exception as e:  # noqa: BLE001 — any build failure is a finding
        return [v(f"contract build failed: {type(e).__name__}: {e}")]
    path, line = _contract_site(fn)
    try:
        args, kwargs = example()
        closed = _trace(fn, args, kwargs)
    except Exception as e:  # noqa: BLE001
        return [v(f"abstract eval failed: {type(e).__name__}: {e}",
                  path, line)]

    out: list[Violation] = []
    counts: Counter = Counter()
    int16_hits: list[str] = []
    while_hits: list[str] = []
    import numpy as np

    for eqn, in_while in walk_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        counts[prim] += 1
        if in_while and prim in ("gather", "dynamic_slice"):
            while_hits.append(prim)
        if contract.no_int16_arithmetic and prim in _ARITHMETIC_PRIMS:
            for var in eqn.invars:
                aval = getattr(var, "aval", None)
                if aval is not None and \
                        getattr(aval, "dtype", None) == np.int16:
                    int16_hits.append(prim)
                    break

    gathers = counts.get("gather", 0)
    scatters = sum(n for p, n in counts.items() if p.startswith("scatter"))
    dyn = counts.get("dynamic_slice", 0)

    if contract.no_gather and gathers:
        out.append(v(
            f"jaxpr contains {gathers} gather primitive(s) but the "
            "contract declares no_gather",
            path, line,
            "computed-index gathers are the TPU slow path (~6x the whole "
            "apply per 64k rows); rewrite as one-hot masked sums / "
            "rolls+selects like ops/apply._apply_core"))
    elif contract.max_gathers is not None and gathers > contract.max_gathers:
        out.append(v(
            f"jaxpr contains {gathers} gather primitive(s), over the "
            f"budget of {contract.max_gathers}",
            path, line,
            "a new computed-index gather crept in; keep gathers confined "
            "to the once-per-wave compaction repack"))
    if contract.no_scatter and scatters:
        out.append(v(
            f"jaxpr contains {scatters} scatter primitive(s) but the "
            "contract declares no_scatter",
            path, line,
            "scatter is the TPU slow path; use jnp.where onto a "
            "precomputed mask instead"))
    elif contract.max_scatters is not None and \
            scatters > contract.max_scatters:
        out.append(v(
            f"jaxpr contains {scatters} scatter primitive(s), over the "
            f"budget of {contract.max_scatters}",
            path, line,
            "a new scatter crept in; keep scatters confined to the "
            "once-per-wave row write-back"))
    if contract.max_dynamic_slices is not None and \
            dyn > contract.max_dynamic_slices:
        out.append(v(
            f"jaxpr contains {dyn} dynamic_slice equation(s), over the "
            f"budget of {contract.max_dynamic_slices}",
            path, line))
    if while_hits:
        out.append(v(
            f"dynamic-index read(s) inside a while body: "
            f"{sorted(set(while_hits))}",
            path, line,
            "a computed-index read in a device loop is K-amplified; "
            "hoist it or use a static roll/select form"))
    if int16_hits:
        out.append(v(
            f"arithmetic on int16 operands: {sorted(set(int16_hits))}",
            path, line,
            "widen explicitly with .astype(jnp.int32) before math — "
            "silent promotion hides wire-width bugs (see the packed-wave "
            "unpack in service/tpu_applier.py)"))
    if contract.single_jit:
        out.extend(_check_single_jit(contract, fn, example, path, line, v))
    return out


def _check_single_jit(contract, fn, example, path, line, v
                      ) -> list[Violation]:
    """Run the kernel twice with same-shape inputs; the compilation
    cache must grow by at most one entry total (one compile, no
    recompile on the second call)."""
    import jax

    jf = fn if hasattr(fn, "_cache_size") else jax.jit(fn)
    try:
        with warnings.catch_warnings():
            # CPU ignores buffer donation; that warning is not a finding
            warnings.simplefilter("ignore")
            args, kwargs = example()
            jax.block_until_ready(jf(*args, **kwargs))
            after_first = jf._cache_size()
            args, kwargs = example()
            jax.block_until_ready(jf(*args, **kwargs))
            after_second = jf._cache_size()
    except Exception as e:  # noqa: BLE001
        return [v(f"single_jit execution failed: {type(e).__name__}: {e}",
                  path, line)]
    if after_second != after_first:
        return [v(
            f"recompile on same-shape inputs: compilation cache grew "
            f"{after_first} -> {after_second} across two identical calls",
            path, line,
            "look for unhashable/py-object statics, weak-type churn, or "
            "a closure rebuilt per call — 'everything under one jit' is "
            "a load-bearing claim (ARCHITECTURE.md)")]
    return []


def check_kernels(registry: Optional[dict] = None,
                  required: tuple = REQUIRED_KERNELS) -> list[Violation]:
    """The full pass: registry coverage + every contract's invariants."""
    if registry is None:
        registry = load_registry()
    out = []
    for name in required:
        if name not in registry:
            out.append(Violation(
                pass_name="jaxpr", path="fluidframework_tpu", line=0,
                message=f"required hot-path kernel '{name}' is not "
                        "registered under a kernel contract",
                suggestion="restore its @kernel_contract / "
                           "register_kernel_contract registration"))
    for name in sorted(registry):
        out.extend(check_contract(registry[name]))
    return out


def _repo_root() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
