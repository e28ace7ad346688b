"""Storage-tier hot-path lint.

Two checks, both born with the columnar segment store (PR 6):

- **json ban**: the storage hot-path modules (``service/durable_log.py``,
  ``service/segment_store.py``, ``native/oplog.py``) may not import
  ``json`` or call ``json.dumps``/``json.loads``. Per-record JSON codecs
  are exactly the cost the segment store exists to remove; every legacy
  shape lives in ``service/log_compat.py`` (the ONE exempted home, whose
  callers count trips under ``storage.log.legacy_json``). The lint also
  asserts the shim module exists — deleting it without a migration would
  silently re-scatter json across the tier.
- **declared storage metrics**: every literal ``storage.*`` name passed
  to ``.inc(...)``/``.observe(...)`` in the library must be in
  ``STORAGE_METRICS``, and every declared name must appear somewhere.
  Dashboards and the net-smoke gates key on these exact strings; a typo
  ("storage.segment.append") would scrape as a new always-zero series
  while the gate starves.
"""

from __future__ import annotations

import ast
import os
from typing import Optional

from .report import Violation

#: Modules banned from json (repo-relative). log_compat.py is the shim
#: for the log lane; summary_trees.py is the snapshot lane's exempted
#: legacy-tree twin (its callers count under storage.snapshot.legacy_tree).
JSON_BANNED = (
    os.path.join("fluidframework_tpu", "service", "durable_log.py"),
    os.path.join("fluidframework_tpu", "service", "segment_store.py"),
    os.path.join("fluidframework_tpu", "native", "oplog.py"),
    os.path.join("fluidframework_tpu", "protocol", "snapcols.py"),
)

COMPAT_SHIM = os.path.join("fluidframework_tpu", "service", "log_compat.py")

#: The storage tier's metric namespace, declared in one place.
STORAGE_METRICS = frozenset({
    "storage.segment.appends",    # segment blocks appended (both lanes' tears re-append)
    "storage.segment.decodes",    # SEG_COLS payloads decoded (backfill must NOT move this)
    "storage.segment.torn",       # chaos torn-tails left + recovered on a segment stream
    "storage.segment.reader_builds",  # SegmentReaders built (a read-only tail builds none)
    "storage.tail.streams",       # seg-lane streams a read-only poll refreshed (one native call)
    "storage.tail.grown",         # of those, streams whose block count changed
    "storage.tail.blocks",        # blocks a read-only tail read through the batch read
    "storage.backfill.byterange", # raw block payloads served by delta_blocks
    "storage.log.legacy_json",    # deltas-lane records still riding the compat shim
    # snapshot fast-boot plane (the net-smoke catch-up gate keys on these)
    "storage.snapshot.encodes",        # framed-chunk cache fills (once per version)
    "storage.snapshot.cache_hits",     # joins served from already-framed bytes
    "storage.snapshot.served",         # snapshot boots served columnar
    "storage.snapshot.legacy_tree",    # whole-tree JSON shim trips (deprecation gauge)
    "storage.snapshot.chunks_written", # chunk blobs uploaded by the summarizer
    "storage.snapshot.chunks_reused",  # content-addressed dedupe across generations
})

_METHODS = ("inc", "observe")


def _check_json_ban(path: str, rel: str, tree: ast.AST) -> list[Violation]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            mod = getattr(node, "module", None)
            names = [a.name for a in node.names]
            if mod == "json" or "json" in names:
                out.append(Violation(
                    pass_name="storage", path=rel, line=node.lineno,
                    message="json import in a storage hot-path module",
                    suggestion="route legacy shapes through "
                               "service/log_compat.py (the exempted shim)"))
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            func = node.func
            if (func.attr in ("dumps", "loads")
                    and isinstance(func.value, ast.Name)
                    and func.value.id == "json"):
                out.append(Violation(
                    pass_name="storage", path=rel, line=node.lineno,
                    message=f"json.{func.attr} on the storage hot path",
                    suggestion="use the columnar segment codec or "
                               "service/log_compat.py"))
    return out


def _iter_metric_names(tree: ast.AST):
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METHODS and node.args):
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                yield node.lineno, arg.value


def check_storage(repo_root: Optional[str] = None) -> list[Violation]:
    repo_root = repo_root or _repo_root()
    out: list[Violation] = []

    if not os.path.exists(os.path.join(repo_root, COMPAT_SHIM)):
        out.append(Violation(
            pass_name="storage", path=COMPAT_SHIM, line=1,
            message="legacy-codec shim module is missing: the json ban "
                    "on the storage tier has nowhere to point",
            suggestion="restore service/log_compat.py (or migrate every "
                       "legacy record shape first)"))

    seen: set[str] = set()
    lib_root = os.path.join(repo_root, "fluidframework_tpu")
    for dirpath, dirnames, files in os.walk(lib_root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "build", "fixtures")]
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, repo_root)
            with open(path) as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError:
                    continue  # the hygiene pass reports syntax errors
            if rel in JSON_BANNED:
                out.extend(_check_json_ban(path, rel, tree))
            for line, name in _iter_metric_names(tree):
                if not name.startswith("storage."):
                    continue
                seen.add(name)
                if name not in STORAGE_METRICS:
                    out.append(Violation(
                        pass_name="storage", path=rel, line=line,
                        message=f'undeclared storage metric "{name}"',
                        suggestion="add it to STORAGE_METRICS in "
                                   "tools/fluidlint/storage_check.py (or "
                                   "fix the typo)"))
    for name in sorted(STORAGE_METRICS - seen):
        out.append(Violation(
            pass_name="storage", path="tools/fluidlint/storage_check.py",
            line=1,
            message=f'declared storage metric "{name}" is never '
                    "incremented anywhere in the library",
            suggestion="wire it up or drop it from STORAGE_METRICS"))
    return out


def _repo_root() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
