"""Metric-name lint: the dotted ``tier.noun.verb`` convention.

Two checks over the library package:

- **metric names**: every string-literal first argument to ``.inc(...)``,
  ``.observe(...)``, ``.set_gauge(...)`` or ``.observe_windowed(...)``
  (Counters or MetricsRegistry, same surface) must be dotted lowercase
  with 3–4 segments — ``driver.submit.coalesced``,
  ``chaos.recovered.orderer_restart``. A scrape namespace where half the
  names are ``opsDone`` and half are ``driver.ops.done`` cannot be
  queried; the convention is only worth having if it is total. F-strings
  and computed names are skipped (the detailed per-point chaos counters
  compose their suffix at runtime).
- **locked families**: the ``obs.slo.*`` and ``net.admission.*``
  namespaces are alert-surface contracts — dashboards and the overload
  bench key on the exact member set. A new name under a locked prefix
  must be added to ``LOCKED_FAMILIES`` in
  ``tools/fluidlint/registries.py`` in the same change, or
  the lint refuses it (spelling drift like ``net.admission.dropped`` vs
  the canonical ``net.admission.shed`` is exactly the bug this catches).
- **span names**: every string-literal first argument to ``span(...)``
  or ``record_span(...)`` must be a key of ``SPANS`` in
  ``utils/telemetry.py``, and of ``sample(...)`` a key of ``SAMPLES``
  — the closed catalogs the trace reductions key on. An uncatalogued
  name would pass silently while tracing is off and raise only in the
  traced run that was meant to explain a stall.
- **Counters construction**: ``Counters(...)`` may only be constructed
  in ``utils/telemetry.py`` (its home) and ``obs/metrics.py`` (the
  registry factory). Everywhere else must go through
  ``obs.tier_counters(tier)`` so the instance lands in the process-wide
  scrape — a bare ``Counters()`` is telemetry the scrape silently never
  sees.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable, Optional

from .registries import HOPS_HOME as SPANS_HOME
from .registries import LOCKED_FAMILIES  # noqa: F401 — re-exported
from .registries import load_span_catalogs
from .report import Violation

#: Swept directories (repo-relative). Tests and tools construct Counters
#: to exercise the mechanism itself and are deliberately out of scope.
METRIC_ROOTS = ("fluidframework_tpu",)

#: Files allowed to construct Counters directly.
COUNTERS_HOMES = (
    os.path.join("fluidframework_tpu", "utils", "telemetry.py"),
    os.path.join("fluidframework_tpu", "obs", "metrics.py"),
)

#: dotted lowercase, 3–4 segments: tier.noun.verb(.qualifier)
NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+){2,3}$")

_METHODS = ("inc", "observe", "set_gauge", "observe_windowed")

#: callee name -> the telemetry catalog its literal name must be in
_CATALOGED = {"span": "SPANS", "record_span": "SPANS", "sample": "SAMPLES"}


def _callee(func) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _py_files(root: str) -> Iterable[str]:
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "build", "fixtures")]
        for fn in sorted(files):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def check_file(path: str, repo_root: Optional[str] = None,
               catalogs: Optional[dict] = None) -> list[Violation]:
    repo_root = repo_root or _repo_root()
    if catalogs is None:
        catalogs = load_span_catalogs(repo_root)
    rel = os.path.relpath(path, repo_root)
    with open(path) as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            return []  # the hygiene pass reports syntax errors
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if (isinstance(func, ast.Attribute) and func.attr in _METHODS
                and node.args):
            arg = node.args[0]
            # only literal names are checkable; f-strings / computed
            # names (the per-point chaos counters) are skipped
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                name = arg.value
                if not NAME_RE.match(name):
                    out.append(Violation(
                        pass_name="metric-name", path=rel,
                        line=node.lineno,
                        message=f'metric name "{name}" breaks the dotted '
                                "tier.noun.verb convention (3-4 lowercase "
                                "segments)",
                        suggestion="rename to e.g. "
                                   '"driver.submit.coalesced"'))
                else:
                    for prefix, members in LOCKED_FAMILIES.items():
                        if name.startswith(prefix) and name not in members:
                            out.append(Violation(
                                pass_name="metric-name", path=rel,
                                line=node.lineno,
                                message=f'"{name}" is not a registered '
                                        f"member of the locked "
                                        f'"{prefix}*" family '
                                        f"(members: "
                                        f"{', '.join(sorted(members))})",
                                suggestion="add it to LOCKED_FAMILIES in "
                                           "tools/fluidlint/"
                                           "registries.py if the "
                                           "contract change is "
                                           "intentional"))
        table = _CATALOGED.get(_callee(func))
        if (table is not None and catalogs is not None and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value not in catalogs[table]):
            out.append(Violation(
                pass_name="metric-name", path=rel, line=node.lineno,
                message=f'"{node.args[0].value}" is not in the telemetry '
                        f"{table} catalog (utils/telemetry.py)",
                suggestion=f"fix the name, or add it to {table} with what "
                           "it times in the same change"))
        if (isinstance(func, ast.Name) and func.id == "Counters"
                and rel not in COUNTERS_HOMES):
            out.append(Violation(
                pass_name="metric-name", path=rel, line=node.lineno,
                message="bare Counters() construction bypasses the "
                        "metrics registry (invisible to the scrape)",
                suggestion="use obs.tier_counters(tier) so the instance "
                           "is labeled and scraped"))
    return out


def check_metrics(repo_root: Optional[str] = None,
                  roots: tuple = METRIC_ROOTS) -> list[Violation]:
    repo_root = repo_root or _repo_root()
    out: list[Violation] = []
    catalogs = load_span_catalogs(repo_root)
    if catalogs is None and os.path.exists(
            os.path.join(repo_root, SPANS_HOME)):
        out.append(Violation(
            pass_name="metric-name", path=SPANS_HOME, line=1,
            message="SPANS or SAMPLES is missing or not a pure dict "
                    "literal — span names cannot be checked",
            suggestion="keep both literal dicts of str -> str"))
    for r in roots:
        root = os.path.join(repo_root, r)
        if not os.path.isdir(root):
            continue
        for path in _py_files(root):
            out.extend(check_file(path, repo_root, catalogs))
    return out


def _repo_root() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
