"""Repo-wide hygiene lints (ride along with the wire pass).

Three checks, each of which has bitten a JAX service codebase before:

- **bare ``except:``** — swallows ``KeyboardInterrupt``/``SystemExit``
  and masks device errors as empty state; always name the exception.
- **mutable default args** — ``def f(x=[])`` shares one list across
  calls; in a long-lived service process that is cross-request state.
- **``jnp`` calls at module import time** — a module-scope
  ``jnp.zeros(...)`` initializes the JAX backend as a side effect of
  ``import``, which on a TPU host grabs the device (and ~seconds of
  startup) for every process that merely imports the library. The
  library package must stay import-silent; build arrays lazily.
"""

from __future__ import annotations

import ast
import os
from typing import Iterable, Optional

from .report import Violation

#: Directories swept for bare-except / mutable-default (repo-relative).
HYGIENE_ROOTS = ("fluidframework_tpu", "tools", "examples", "tests")

#: The library package: also checked for import-time jnp calls.
IMPORT_SILENT_ROOTS = ("fluidframework_tpu",)


def _py_files(root: str) -> Iterable[str]:
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "build", "fixtures")]
        for fn in sorted(files):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def _module_level_calls(tree: ast.Module) -> Iterable[ast.Call]:
    """Call nodes that execute at import time: anything not inside a
    function/lambda body (class bodies DO execute at import)."""

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, ast.Call):
                yield child
            yield from walk(child)

    yield from walk(tree)


def check_file(path: str, repo_root: Optional[str] = None,
               import_silent: bool = False) -> list[Violation]:
    repo_root = repo_root or _repo_root()
    rel = os.path.relpath(path, repo_root)
    with open(path) as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return [Violation(pass_name="hygiene", path=rel,
                              line=e.lineno or 0,
                              message=f"syntax error: {e.msg}")]
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            out.append(Violation(
                pass_name="hygiene", path=rel, line=node.lineno,
                message="bare `except:` swallows KeyboardInterrupt and "
                        "masks device errors",
                suggestion="catch `Exception` (or the specific error) "
                           "instead"))
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    out.append(Violation(
                        pass_name="hygiene", path=rel, line=node.lineno,
                        message=f"mutable default argument in "
                                f"`{node.name}` is shared across calls",
                        suggestion="default to None and construct inside "
                                   "the function"))
    if import_silent:
        for call in _module_level_calls(tree):
            name = _dotted(call.func)
            if name.startswith("jnp.") or name.startswith("jax.numpy."):
                out.append(Violation(
                    pass_name="hygiene", path=rel, line=call.lineno,
                    message=f"`{name}(...)` at module import time "
                            "initializes the JAX backend on import",
                    suggestion="build device arrays lazily (inside a "
                               "function, or a cached builder)"))
    return out


def check_hygiene(repo_root: Optional[str] = None,
                  roots: tuple = HYGIENE_ROOTS,
                  import_silent_roots: tuple = IMPORT_SILENT_ROOTS
                  ) -> list[Violation]:
    repo_root = repo_root or _repo_root()
    out: list[Violation] = []
    for r in roots:
        root = os.path.join(repo_root, r)
        if not os.path.isdir(root):
            continue
        silent = r in import_silent_roots
        for path in _py_files(root):
            out.extend(check_file(path, repo_root, import_silent=silent))
    # top-level scripts (chip_smoke.py, __graft_entry__.py, ...)
    for fn in sorted(os.listdir(repo_root)):
        if fn.endswith(".py"):
            out.extend(check_file(os.path.join(repo_root, fn), repo_root))
    return out


def _dotted(node: ast.AST) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _repo_root() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
