"""Pass 1 — layer-check: the package import DAG, as the single source
of truth.

Ref: tools/build-tools/src/layerCheck — the reference CI fails any build
whose packages import across the declared layer boundaries, and its
docs/PACKAGES.md layer listing is GENERATED from the same table, so the
docs can never drift from what CI enforces. This module is that table
for our tree: ``tests/test_layering.py`` delegates here, ``python -m
tools.fluidlint --emit-packages-md`` regenerates ``PACKAGES.md``, and
the default lint run fails when the checked-in listing is stale.

Layering (bottom → top), mirroring SURVEY §1's layer map:

    utils                (L1 base utils / telemetry / kernel contracts)
    protocol             (L0 defs + L2 shared consensus kernel)
    mergetree            (L6 CRDT core)
    ops, parallel        (TPU kernels / sharding over the mergetree model)
    dds                  (L6 DDS catalog)
    runtime              (L5)
    loader               (L4; the loader imports DRIVER interfaces)
    driver               (L3 — may bind to service for the local driver)
    framework            (L7)
    service              (S-layers: its own branch; may use protocol,
                          utils, mergetree-adjacent kernels, driver wire
                          helpers — but never runtime/loader/framework)
    replay, native       (tools / bindings)
"""

from __future__ import annotations

import ast
import os
from typing import Iterable, Optional

from .report import Violation

#: Default package root (repo-relative) the real-tree check walks.
PACKAGE = "fluidframework_tpu"

#: subpackage → the set of sibling subpackages it may import from.
#: An import of a package not in its set is a layering violation.
#: THE single source of truth: tests/test_layering.py asserts over this
#: table, and PACKAGES.md is generated from it.
ALLOWED = {
    "utils": set(),
    # the observability plane sits just above utils: registry + flight
    # recorder; any tier may report INTO it, it imports nothing back
    "obs": {"utils"},
    "protocol": {"utils"},
    "mergetree": {"protocol", "utils"},
    "ops": {"mergetree", "protocol", "utils"},
    "parallel": {"ops", "mergetree", "protocol", "utils"},
    "dds": {"mergetree", "ops", "protocol", "utils"},
    "runtime": {"dds", "mergetree", "ops", "protocol", "utils"},
    "loader": {"runtime", "dds", "mergetree", "protocol", "utils",
               "driver"},
    # drivers bind the loader contracts to a service; the local driver
    # reaches into service (the reference's local-driver does the same —
    # localDocumentService.ts binds straight to LocalDeltaConnectionServer)
    "driver": {"protocol", "utils", "service", "mergetree", "obs"},
    "framework": {"loader", "runtime", "dds", "mergetree", "protocol",
                  "utils"},
    # the service branch: protocol + utils + the TPU kernel stack; the
    # wire helpers live in driver (shared transport), NEVER runtime/loader;
    # dds for the applier's host replica of an escalated grid (SharedMatrix)
    "service": {"protocol", "utils", "ops", "parallel", "mergetree",
                "driver", "native", "obs", "dds"},
    "native": {"utils"},
    # obs: the replay tool reports history-first vs legacy whole-log
    # boots into the shared metrics registry (history.replay.legacy)
    "replay": {"loader", "driver", "runtime", "dds", "protocol", "utils",
               "service", "mergetree", "obs"},
    # the fault-injection plane sits beside the service: it may reach the
    # seams it arms (service/driver) and the layers they expose, but NO
    # production layer may import chaos back — the seams stay duck-typed
    # (`fault_plane = None` class attrs / module hooks), so disarmed code
    # has no chaos dependency at all; only tests and the soak import it.
    # loader/runtime: the soak's snapshot campaign boots full containers
    # through the columnar fast-boot plane as its late joiners; parallel:
    # the mesh soak asks for its devices (parallel.mesh.require_devices)
    "chaos": {"service", "driver", "mergetree", "protocol", "utils",
              "obs", "loader", "runtime", "parallel"},
}

#: One-line role per layer, used by the PACKAGES.md generator.
LAYER_DOC = {
    "utils": "base utils: telemetry, metrics, kernel-contract registry",
    "obs": "observability: labeled metrics registry, Prometheus scrape, "
           "flight recorder",
    "protocol": "wire messages, consensus kernel, binary codec",
    "mergetree": "scalar merge-tree CRDT (the readable oracle)",
    "ops": "TPU device kernels: batched apply, doc state, Pallas path",
    "parallel": "mesh construction, doc/segment sharding",
    "dds": "distributed data structure catalog",
    "runtime": "container runtime, datastores, summarizer",
    "loader": "container boot, delta manager, quorum",
    "driver": "local / network / file drivers (wire transport)",
    "framework": "aqueduct: DataObject, undo-redo, interceptions",
    "service": "deli, scriptorium, scribe, TPU applier, front end, "
               "placement control plane",
    "native": "C++ durable op log + chunk store bindings",
    "replay": "replay tool + snapshot-regression corpus",
    "chaos": "deterministic fault injection + convergence invariant monitor",
}


def sibling_imports(path: str, root: str) -> list[tuple[str, int, str]]:
    """Sibling subpackages imported by ``path``: [(pkg, lineno, stmt)].

    ``root`` is the package directory the layering is declared over;
    both absolute ``package.sub`` imports (for the package named by the
    root dir) and relative ``..sub`` imports resolve to ``sub``.
    """
    package_name = os.path.basename(os.path.normpath(root))
    with open(path) as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    lines = src.splitlines()
    depth_from_root = os.path.relpath(path, root).count(os.sep)
    out = []

    def stmt(node):
        return lines[node.lineno - 1].strip() if node.lineno <= len(lines) \
            else ""

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.level == 0:
                mod = node.module or ""
                if mod.startswith(package_name + "."):
                    out.append((mod.split(".")[1], node.lineno, stmt(node)))
            else:
                # relative: level 1 inside pkg/x.py = same package;
                # level 2 = the framework root (..sibling)
                if node.level == depth_from_root + 1 and node.module:
                    out.append((node.module.split(".")[0], node.lineno,
                                stmt(node)))
                elif node.level > depth_from_root + 1:
                    out.append(("<outside-package>", node.lineno,
                                stmt(node)))
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith(package_name + "."):
                    out.append((alias.name.split(".")[1], node.lineno,
                                stmt(node)))
    return out


def package_files(root: str, allowed: dict) -> Iterable[tuple[str, str]]:
    """(subpackage, file path) for every .py under a classified layer."""
    for pkg in sorted(allowed):
        pkg_dir = os.path.join(root, pkg)
        if not os.path.isdir(pkg_dir):
            continue
        for dirpath, _, files in os.walk(pkg_dir):
            for fn in sorted(files):
                if fn.endswith(".py"):
                    yield pkg, os.path.join(dirpath, fn)


def _suggest(pkg: str, dep: str, allowed: dict) -> str:
    importers = sorted(layer for layer, deps in allowed.items()
                       if dep in deps)
    if importers:
        return (f"'{dep}' may only be imported from "
                f"{{{', '.join(importers)}}}; move the code there, invert "
                f"the dependency, or (deliberately) widen ALLOWED['{pkg}'] "
                f"in tools/fluidlint/layers.py")
    return (f"no layer may import '{dep}'; invert the dependency or "
            f"(deliberately) widen ALLOWED['{pkg}'] in "
            f"tools/fluidlint/layers.py")


def check_layers(root: Optional[str] = None,
                 allowed: Optional[dict] = None,
                 repo_root: Optional[str] = None) -> list[Violation]:
    """AST import walk over every classified layer; one Violation per
    cross-layer import, with file:line, the offending statement, and the
    layers the import would be legal from."""
    repo_root = repo_root or _repo_root()
    root = root or os.path.join(repo_root, PACKAGE)
    allowed = allowed if allowed is not None else ALLOWED
    violations = []
    for pkg, path in package_files(root, allowed):
        ok = allowed[pkg] | {pkg}
        for dep, lineno, stmt in sibling_imports(path, root):
            # only sibling SUBPACKAGES are layered; top-level modules
            # (config.py — the cross-cutting unified registry) are free
            if dep not in allowed or dep in ok:
                continue
            rel = os.path.relpath(path, repo_root)
            violations.append(Violation(
                pass_name="layers", path=rel, line=lineno,
                message=f"layer '{pkg}' may not import '{dep}' "
                        f"({stmt})",
                suggestion=_suggest(pkg, dep, allowed)))
    return violations


def check_classified(root: Optional[str] = None,
                     allowed: Optional[dict] = None,
                     repo_root: Optional[str] = None) -> list[Violation]:
    """A new subpackage must be placed in the layer map explicitly."""
    repo_root = repo_root or _repo_root()
    root = root or os.path.join(repo_root, PACKAGE)
    allowed = allowed if allowed is not None else ALLOWED
    found = {d for d in os.listdir(root)
             if os.path.isdir(os.path.join(root, d))
             and not d.startswith("__")}
    return [Violation(
        pass_name="layers", path=os.path.relpath(root, repo_root), line=0,
        message=f"subpackage '{d}' missing from the layer map",
        suggestion="add it to ALLOWED in tools/fluidlint/layers.py "
                   "(and to PACKAGES.md via --emit-packages-md)")
        for d in sorted(found - set(allowed))]


def _topo_layers(allowed: dict) -> list[str]:
    """Layers bottom-up (deps before dependents), name-stable."""
    out, placed = [], set()
    pending = dict(allowed)
    while pending:
        ready = sorted(p for p, deps in pending.items()
                       if set(deps) - {p} <= placed)
        if not ready:  # cycle: emit the rest sorted, deterministic
            out.extend(sorted(pending))
            break
        for p in ready:
            out.append(p)
            placed.add(p)
            del pending[p]
    return out


def emit_packages_md(root: Optional[str] = None,
                     allowed: Optional[dict] = None,
                     repo_root: Optional[str] = None) -> str:
    """The generated layer listing (the reference's PACKAGES.md analog).

    Deterministic over (ALLOWED, tree): regenerating on an unchanged
    tree is byte-identical, so CI can diff it against the checked-in
    copy."""
    repo_root = repo_root or _repo_root()
    root = root or os.path.join(repo_root, PACKAGE)
    allowed = allowed if allowed is not None else ALLOWED
    package_name = os.path.basename(os.path.normpath(root))
    modules: dict[str, list[str]] = {pkg: [] for pkg in allowed}
    for pkg, path in package_files(root, allowed):
        rel = os.path.relpath(path, os.path.join(root, pkg))
        if rel != "__init__.py":
            modules[pkg].append(rel.replace(os.sep, "/"))
    lines = [
        "# PACKAGES",
        "",
        "<!-- GENERATED by `python -m tools.fluidlint --emit-packages-md` "
        "from tools/fluidlint/layers.py — do not edit by hand. -->",
        "",
        f"Layer listing for `{package_name}/`, bottom-up. Each layer may "
        "import only the layers listed in its **may import** set; "
        "`python -m tools.fluidlint` (pass 1) fails the build on any "
        "other cross-layer import.",
        "",
    ]
    for pkg in _topo_layers(allowed):
        deps = sorted(allowed[pkg])
        lines.append(f"## {pkg}")
        lines.append("")
        doc = LAYER_DOC.get(pkg)
        if doc:
            lines.append(doc)
            lines.append("")
        lines.append("**may import:** "
                     + (", ".join(f"`{d}`" for d in deps) if deps
                        else "(nothing — bottom layer)"))
        lines.append("")
        mods = sorted(modules.get(pkg, []))
        if mods:
            lines.append("**modules:** "
                         + ", ".join(f"`{m}`" for m in mods))
            lines.append("")
    return "\n".join(lines)


def check_packages_md(md_path: Optional[str] = None,
                      repo_root: Optional[str] = None) -> list[Violation]:
    """Fail when the checked-in PACKAGES.md is stale (or missing)."""
    repo_root = repo_root or _repo_root()
    md_path = md_path or os.path.join(repo_root, "PACKAGES.md")
    want = emit_packages_md(repo_root=repo_root)
    try:
        with open(md_path) as f:
            have = f.read()
    except OSError:
        have = None
    if have == want:
        return []
    state = "missing" if have is None else "stale"
    return [Violation(
        pass_name="layers", path=os.path.relpath(md_path, repo_root),
        line=0,
        message=f"generated layer listing is {state}",
        suggestion="run `python -m tools.fluidlint --emit-packages-md` "
                   "and commit the result")]


def _repo_root() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
