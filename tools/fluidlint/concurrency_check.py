"""Pass 8 — concurrency contracts: thread affinity, lock discipline,
blocking calls, shared state.

Every "found the hard way" bug in PRs 10–13 was a concurrency-
discipline violation, not a logic error: CPU donation silently
serializing dispatch behind ``block_until_ready`` (PR 11), staging-
buffer refills racing in-flight executions until the rotation fence was
keyed to the consuming execution (PR 11), ``tier_counters`` weakrefs
dying under the ticker thread (PR 13), and the rebalancer needing a
loopback ``admin_migrate_part`` RPC because migrations are only sound
on the core's event loop (PR 13). The reference enforces its
architecture with a build-time layer check but has nothing for thread
discipline; this pass is the RacerD / Clang ``-Wthread-safety`` analog
for our tree — annotate the boundaries
(``fluidframework_tpu/utils/affinity.py``), build a package-wide call
graph, propagate execution contexts from every spawn site, and flag
the crossings.

**Contexts** (strings propagated along the call graph):

- ``loop:<name>`` — an asyncio event-loop thread. Seeds: ``async def``
  bodies (``loop:?``), ``call_soon`` / ``call_soon_threadsafe`` /
  ``add_done_callback`` callbacks, and ``@loop_only(name)``
  annotations.
- ``ticker:<name>`` — a daemon ticker thread (``@ticker_thread``).
- ``thread:<name>`` — a ``threading.Thread(target=..., name=...)``.
- ``executor`` — a ``run_in_executor`` offload.

Propagation is conservative: an edge exists only when the callee
resolves unambiguously — ``self.m()`` within the enclosing class (and
package-local bases), bare names via module scope and ``from``
imports, typed receivers (``x = ClassName(...)`` locals and
``self.attr = ClassName(...)`` instance attrs), and otherwise an
attribute call resolves only when exactly ONE function in the package
bears that name. Ambiguity means no edge — this pass must hold a
zero-false-positive bar on the real tree; fixtures are small enough to
resolve fully. Seam calls (``call_soon_threadsafe``,
``run_in_executor``, ``Thread(target=...)``) TRANSFER context to their
callback instead of propagating the caller's, and the loopback
``admin_rpc`` breaks the graph at the socket the way it breaks the
thread coupling at runtime.

**Violation classes:**

- ``CROSS-AFFINITY`` — a ticker/thread/executor context reaches a
  ``@loop_only`` function without going through a registered seam.
- ``BLOCKING-ON-LOOP`` — a blocking call (socket ``sendall``/``recv``,
  ``fcntl.flock``, ``time.sleep``, ``block_until_ready``, subprocess
  waits, the durable log's mmap flush, or any ``@blocking``-annotated
  function) reachable from an event-loop context. Each blocker carries
  the PR that made it load-bearing.
- ``UNFENCED-SHARED-STATE`` — an instance attribute written from ≥2
  distinct concrete contexts with no common lock fence (lexical
  ``with <lock>:`` or ``@holds_lock``) and no waiver.
- ``LOCK-ORDER`` — registered locks must be acquired in the single
  global order (``registries.LOCK_ORDER``), checked over lexical
  ``with`` nesting and ``@holds_lock`` call edges.

Intentional exceptions live in ``concurrency_waivers.py`` — each with
a one-line justification the report prints, and a waiver that stops
matching anything is itself flagged as stale.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Iterable, Optional

from .registries import LOCK_ORDER, LOCK_RANK
from .report import Violation

#: Swept package roots (repo-relative), same scope as the other passes.
PACKAGE_ROOTS = ("fluidframework_tpu",)

# ------------------------------------------------------------ blockers

#: dotted call -> provenance (the PR that made the blocker load-bearing
#: on a near-loop path; the report prints it so a reader knows which
#: hard-way bug the rule encodes).
BLOCKING_DOTTED = {
    "time.sleep": "thread pacing (PR 2 chaos delays, PR 13 tickers) — "
                  "on a loop use `await asyncio.sleep`",
    "socket.create_connection": "synchronous dial (PR 10's loopback "
                                "admin_rpc made these load-bearing)",
    "subprocess.run": "subprocess wait",
    "subprocess.check_output": "subprocess wait",
    "subprocess.check_call": "subprocess wait",
    "fcntl.flock": "file-lock wait (PR 10 epoch-table flock)",
}

#: attribute name (any receiver) -> provenance.
BLOCKING_ATTRS = {
    "sendall": "synchronous socket write (PR 10 admin_rpc)",
    "recv": "synchronous socket read (PR 10 admin_rpc)",
    "block_until_ready": "device sync — PR 11's donation-on-CPU bug "
                         "serialized dispatch exactly here",
    "communicate": "subprocess wait",
}

#: dotted-suffix -> provenance: the durable log's mmap-backed surface.
BLOCKING_SUFFIXES = {
    ".log.flush": "durable-log mmap flush (PR 6 columnar storage; "
                  "PR 11 made flushes per-batch, not per-frame)",
}

#: Callees that TRANSFER context rather than running the callback in
#: the caller's context: the function argument is seeded separately.
SEAM_CALLS = frozenset({
    "call_soon", "call_soon_threadsafe", "run_coroutine_threadsafe",
    "run_in_executor", "create_task", "add_done_callback",
    "ensure_future",
})

#: Registered loopback RPC seams: a ticker actuating through one of
#: these reaches the loop over a socket, not a call edge — named here
#: so CROSS-AFFINITY suggestions can point at the sanctioned pattern.
LOOPBACK_SEAMS = ("service.placement_plane.admin_rpc",)

# ------------------------------------------------------- lock site maps

#: `with <fn>(...)` call names -> registered lock.
WITH_CALL_LOCKS = {"_flock": "epoch_table_flock"}

#: `with self.<method>(...)` per class -> registered lock (the lease
#: lock is a contextmanager METHOD, the others are Lock attributes).
CLASS_CALL_LOCKS = {("PlacementDir", "_lock"): "partition_claim_flock"}

#: `with self.<attr>:` per class -> registered lock.
CLASS_ATTR_LOCKS = {
    ("Journal", "_lock"): "journal_lock",
}

_AFFINITY_DECOS = ("loop_only", "ticker_thread", "any_thread",
                   "holds_lock", "blocking")


# ----------------------------------------------------------- collection

@dataclass
class _Fn:
    qual: str                    # module.Class.fn / module.fn
    module: str                  # dotted module (package-relative)
    cls: Optional[str]
    name: str
    path: str                    # repo-relative
    lineno: int
    is_async: bool = False
    affinity: Optional[tuple] = None      # ("loop"|"ticker"|"any", name)
    holds: tuple = ()                     # @holds_lock names
    blocking: Optional[str] = None        # @blocking reason
    calls: list = field(default_factory=list)    # (ref, line, held)
    blocker_hits: list = field(default_factory=list)  # (line, what, why)
    writes: list = field(default_factory=list)   # (attr, line, fences)
    acquires: list = field(default_factory=list)  # (lock, line, held)
    seam_args: set = field(default_factory=set)  # callback names seamed
    contexts: set = field(default_factory=set)
    seeds: dict = field(default_factory=dict)    # ctx -> seed reason


def _dotted(node: ast.AST) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _py_files(root: str) -> Iterable[str]:
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "build", "fixtures")]
        for fn in sorted(files):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def _affinity_of(deco_list):
    """(affinity, holds, blocking) from the decorator list — matched by
    name, not import, so un-imported fixture trees are checkable."""
    affinity, holds, blocking = None, [], None
    for d in deco_list:
        call_args = []
        if isinstance(d, ast.Call):
            call_args = [a.value for a in d.args
                         if isinstance(a, ast.Constant)]
            d = d.func
        name = _dotted(d).rsplit(".", 1)[-1]
        if name not in _AFFINITY_DECOS:
            continue
        if name == "loop_only":
            affinity = ("loop", call_args[0] if call_args else "core")
        elif name == "ticker_thread":
            affinity = ("ticker", call_args[0] if call_args else "?")
        elif name == "any_thread":
            affinity = ("any", "")
        elif name == "holds_lock" and call_args:
            holds.append(call_args[0])
        elif name == "blocking":
            blocking = call_args[0] if call_args else "blocking I/O"
    return affinity, tuple(holds), blocking


class _Package:
    """The parsed package: functions, classes, imports, spawn seeds."""

    def __init__(self):
        self.fns: dict[str, _Fn] = {}
        self.by_name: dict[str, list] = {}
        self.mod_scope: dict[str, dict] = {}     # module -> name -> qual
        self.mod_classes: dict[str, dict] = {}   # module -> cls -> meths
        self.class_bases: dict[tuple, list] = {}  # (mod, cls) -> [names]
        self.attr_types: dict[tuple, dict] = {}  # (mod, cls) -> attr->cls
        self.imports: dict[str, dict] = {}       # module -> local -> tgt
        self.spawns: list = []                   # (ref, ctx, reason, fn)

    def add_fn(self, fn: _Fn):
        self.fns[fn.qual] = fn
        self.by_name.setdefault(fn.name, []).append(fn.qual)


def _class_name_of(node) -> Optional[str]:
    """`ClassName(...)` constructor calls: the (unqualified) class."""
    if isinstance(node, ast.Call):
        name = _dotted(node.func).rsplit(".", 1)[-1]
        if name and name[0].isupper():
            return name
    return None


class _BodyWalk:
    """One function body: calls with held-lock sets, self-writes with
    fence sets, direct blocker hits, spawn/seam seeds, nested defs."""

    def __init__(self, fn: _Fn, pkg: _Package, cls: Optional[str]):
        self.fn = fn
        self.pkg = pkg
        self.cls = cls
        self.held: list[str] = list(fn.holds)
        self.fences: list[str] = list(fn.holds)
        self.local_types: dict[str, str] = {}
        self.nested: list = []

    # -- lock naming -------------------------------------------------
    def _lock_of_with_item(self, expr) -> Optional[str]:
        if isinstance(expr, ast.Call):
            d = _dotted(expr.func)
            base = d.rsplit(".", 1)[-1]
            if base in WITH_CALL_LOCKS:
                return WITH_CALL_LOCKS[base]
            if d.startswith("self.") and self.cls:
                return CLASS_CALL_LOCKS.get((self.cls, base))
            return None
        d = _dotted(expr)
        if d.startswith("self.") and d.count(".") == 1 and self.cls:
            attr = d.split(".", 1)[1]
            hit = CLASS_ATTR_LOCKS.get((self.cls, attr))
            if hit:
                return hit
            low = attr.lower()
            if any(k in low for k in ("lock", "cv", "cond", "wake")):
                return f"{self.cls}.{attr}"
        return None

    # -- traversal ---------------------------------------------------
    def walk(self, body) -> None:
        for stmt in body:
            self._visit(stmt)

    def _visit(self, node) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.nested.append(node)
            return
        if isinstance(node, ast.Lambda):
            # opaque: a lambda is usually a callback — attributing its
            # body's calls to the enclosing function would claim the
            # wrong execution context (e.g. executor work built inline)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            self._with(node)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            self._assign(node)
            return
        if isinstance(node, ast.Call):
            self._call(node)
            return
        for child in ast.iter_child_nodes(node):
            self._visit(child)

    def _with(self, node) -> None:
        locks = []
        for it in node.items:
            lk = self._lock_of_with_item(it.context_expr)
            if lk:
                locks.append(lk)
            self._visit(it.context_expr)
        for lk in locks:
            if lk in LOCK_RANK:
                held_reg = tuple(h for h in self.held if h in LOCK_RANK)
                self.fn.acquires.append((lk, node.lineno, held_reg))
            self.held.append(lk)
            self.fences.append(lk)
        self.walk(node.body)
        for lk in locks:
            self.held.pop()
            self.fences.pop()

    def _assign(self, node) -> None:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        value = getattr(node, "value", None)
        cls_of_value = _class_name_of(value) if value is not None else None
        for t in targets:
            d = _dotted(t)
            if d.startswith("self.") and d.count(".") == 1:
                attr = d.split(".", 1)[1]
                self.fn.writes.append(
                    (attr, node.lineno, frozenset(self.fences)))
                if cls_of_value and self.fn.name == "__init__" \
                        and self.cls:
                    key = (self.fn.module, self.cls)
                    self.pkg.attr_types.setdefault(key, {})[attr] = \
                        cls_of_value
            elif isinstance(t, ast.Name) and cls_of_value:
                self.local_types[t.id] = cls_of_value
            else:
                self._visit(t)
        if value is not None:
            self._visit(value)

    # -- calls -------------------------------------------------------
    def _fn_ref(self, node) -> Optional[tuple]:
        """A *reference* to a function (callback position): a
        resolution request tuple, or None."""
        d = _dotted(node)
        if not d:
            return None
        if d.startswith("self.") and d.count(".") == 1:
            return ("self", d.split(".", 1)[1], d)
        if "." not in d:
            return ("name", d, d)
        return ("attr", d.rsplit(".", 1)[-1], d)

    def _call(self, node: ast.Call) -> None:
        d = _dotted(node.func)
        base = d.rsplit(".", 1)[-1] if d else ""
        handled = False

        if base == "Thread":
            target, tname = None, None
            for kw in node.keywords:
                if kw.arg == "target":
                    target = self._fn_ref(kw.value)
                elif kw.arg == "name" and isinstance(kw.value,
                                                    ast.Constant):
                    tname = str(kw.value.value)
            if target is not None:
                ctx = f"thread:{tname or target[1]}"
                self.pkg.spawns.append(
                    (target, ctx,
                     f"threading.Thread in {self.fn.qual}", self.fn))
                self.fn.seam_args.add(target[1])
                handled = True
        elif base in SEAM_CALLS:
            cb_args = list(node.args)
            if base == "run_in_executor":
                cb_args = cb_args[1:2]  # (executor, fn, *args)
                ctx, why = "executor", "run_in_executor offload"
            else:
                cb_args = cb_args[:1]
                ctx, why = "loop:?", f"{base} callback"
            for a in cb_args:
                ref = self._fn_ref(a)
                if ref is None and isinstance(a, ast.Call):
                    # create_task(coro(...)): seed the coroutine fn
                    ref = self._fn_ref(a.func)
                if ref is not None:
                    self.pkg.spawns.append(
                        (ref, ctx, f"{why} in {self.fn.qual}", self.fn))
                    self.fn.seam_args.add(ref[1])
            handled = True

        if not handled and d:
            if self.fn.blocking is None:
                if d in BLOCKING_DOTTED:
                    self.fn.blocker_hits.append(
                        (node.lineno, f"{d}()", BLOCKING_DOTTED[d]))
                elif base in BLOCKING_ATTRS and "." in d:
                    self.fn.blocker_hits.append(
                        (node.lineno, f".{base}()", BLOCKING_ATTRS[base]))
                else:
                    for suffix, why in BLOCKING_SUFFIXES.items():
                        if d.endswith(suffix):
                            self.fn.blocker_hits.append(
                                (node.lineno, d, why))
            ref = self._fn_ref(node.func)
            if ref is not None:
                kind, name, dotted = ref
                if kind == "attr":
                    parts = dotted.split(".")
                    recv_cls = None
                    if parts[0] == "self" and len(parts) == 3:
                        recv_cls = self.pkg.attr_types.get(
                            (self.fn.module, self.cls), {}).get(parts[1])
                    elif len(parts) == 2:
                        recv_cls = self.local_types.get(parts[0])
                    if recv_cls is not None:
                        ref = ("typed", name, recv_cls)
                held_reg = tuple(h for h in self.held if h in LOCK_RANK)
                self.fn.calls.append((ref, node.lineno, held_reg))

        for a in node.args:
            self._visit(a)
        for kw in node.keywords:
            self._visit(kw.value)
        if isinstance(node.func, ast.Attribute):
            self._visit(node.func.value)  # receivers can contain calls


def _collect_module(pkg: _Package, path: str, rel: str, module: str,
                    root_pkg: str, is_pkg: bool) -> None:
    with open(path) as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            return  # the hygiene pass reports syntax errors
    pkg.mod_scope.setdefault(module, {})
    pkg.imports.setdefault(module, {})

    # function-level imports count too (the tree defers several to the
    # call site to break import cycles); last alias binding wins, which
    # is conservative enough at package scope
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            _collect_import(pkg, module, node, root_pkg, is_pkg)

    def collect_fn(node, cls: Optional[str], parent: Optional[_Fn]):
        if parent is not None:
            qual = f"{parent.qual}.<locals>.{node.name}"
        elif cls:
            qual = f"{module}.{cls}.{node.name}"
        else:
            qual = f"{module}.{node.name}"
        affinity, holds, blocking = _affinity_of(node.decorator_list)
        fn = _Fn(qual=qual, module=module, cls=cls, name=node.name,
                 path=rel, lineno=node.lineno,
                 is_async=isinstance(node, ast.AsyncFunctionDef),
                 affinity=affinity, holds=holds, blocking=blocking)
        pkg.add_fn(fn)
        walker = _BodyWalk(fn, pkg, cls)
        walker.walk(node.body)
        for stmt in walker.nested:
            child = collect_fn(stmt, cls, fn)
            if stmt.name not in fn.seam_args:
                # a nested def runs in the parent's context unless it
                # was handed to a seam (Thread / executor / call_soon)
                fn.calls.append(
                    (("exact", child.qual, child.qual), stmt.lineno, ()))
        return fn

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn = collect_fn(node, None, None)
            pkg.mod_scope[module][node.name] = fn.qual
        elif isinstance(node, ast.ClassDef):
            pkg.mod_scope[module][node.name] = f"{module}.{node.name}"
            methods = {}
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    fn = collect_fn(sub, node.name, None)
                    methods[sub.name] = fn.qual
            pkg.mod_classes.setdefault(module, {})[node.name] = methods
            pkg.class_bases[(module, node.name)] = [
                _dotted(b).rsplit(".", 1)[-1] for b in node.bases]


def _collect_import(pkg: _Package, module: str, node: ast.ImportFrom,
                    root_pkg: str, is_pkg: bool) -> None:
    """Map `from X import f` locals to (target_module, original_name),
    for relative imports and absolute in-package ones."""
    table = pkg.imports[module]
    if node.level > 0:
        parts = module.split(".") if module != root_pkg else []
        # a package __init__ resolves level 1 against itself
        strip = node.level - 1 if is_pkg else node.level
        if strip > len(parts):
            return
        base = parts[:len(parts) - strip] if strip else parts
        target = ".".join(base + (node.module.split(".")
                                  if node.module else []))
    else:
        target = node.module or ""
        if target == root_pkg:
            target = ""
        elif target.startswith(root_pkg + "."):
            target = target[len(root_pkg) + 1:]
        else:
            return  # external import
    for alias in node.names:
        table[alias.asname or alias.name] = (target, alias.name)


# ---------------------------------------------------------- resolution

def _resolve(pkg: _Package, fn: _Fn, ref) -> Optional[str]:
    kind, name, extra = ref
    if kind == "exact":
        return extra if extra in pkg.fns else None
    if kind == "self":
        return _resolve_method(pkg, fn.module, fn.cls, name)
    if kind == "typed":
        for mod, classes in pkg.mod_classes.items():
            if extra in classes:
                hit = _resolve_method(pkg, mod, extra, name)
                if hit:
                    return hit
        return None
    if kind == "name":
        hit = pkg.mod_scope.get(fn.module, {}).get(name)
        if hit and hit in pkg.fns:
            return hit
        imp = pkg.imports.get(fn.module, {}).get(name)
        if imp:
            tgt_mod, orig = imp
            hit = pkg.mod_scope.get(tgt_mod, {}).get(orig)
            if hit and hit in pkg.fns:
                return hit
        return None
    # attr: the unique-name rule over the whole package
    quals = pkg.by_name.get(name, ())
    if len(quals) == 1:
        return quals[0]
    return None


def _resolve_method(pkg: _Package, module: str, cls: Optional[str],
                    name: str) -> Optional[str]:
    seen = set()
    stack = [(module, cls)]
    while stack:
        mod, c = stack.pop()
        if c is None or (mod, c) in seen:
            continue
        seen.add((mod, c))
        hit = pkg.mod_classes.get(mod, {}).get(c, {}).get(name)
        if hit:
            return hit
        for base in pkg.class_bases.get((mod, c), ()):
            if base in pkg.mod_classes.get(mod, {}):
                stack.append((mod, base))
            else:
                homes = [m for m, cs in pkg.mod_classes.items()
                         if base in cs]
                if len(homes) == 1:
                    stack.append((homes[0], base))
    return None


# --------------------------------------------------------- propagation

def _loopish(ctx: str) -> bool:
    return ctx.startswith("loop:")


def _concrete(ctx: str) -> Optional[str]:
    """Collapse for shared-state grouping: all loop contexts are one
    (a tier runs one loop thread)."""
    if _loopish(ctx):
        return "loop"
    if ctx.startswith(("ticker:", "thread:")) or ctx == "executor":
        return ctx
    return None


def _propagate(pkg: _Package):
    """Flow contexts from seeds along resolved edges; record the first
    parent of each (fn, ctx) for witness paths."""
    parent: dict[tuple, tuple] = {}
    work: list[str] = []

    def seed(fn: _Fn, ctx: str, reason: str):
        if ctx not in fn.contexts:
            fn.contexts.add(ctx)
            fn.seeds.setdefault(ctx, reason)
            work.append(fn.qual)

    for fn in pkg.fns.values():
        if fn.affinity:
            kind, name = fn.affinity
            if kind == "loop":
                seed(fn, f"loop:{name}", f"@loop_only({name!r})")
            elif kind == "ticker":
                seed(fn, f"ticker:{name}", f"@ticker_thread({name!r})")
        elif fn.is_async:
            seed(fn, "loop:?", "async def — coroutine bodies run on "
                               "the owning tier's event loop")
    for ref, ctx, reason, src in pkg.spawns:
        tgt = _resolve(pkg, src, ref)
        if tgt is None:
            continue
        fn = pkg.fns[tgt]
        if fn.affinity and fn.affinity[0] in ("loop", "ticker"):
            continue  # declared affinity names the SAME thread; seeding
            # both would double-count one execution context
        seed(fn, ctx, reason)

    edges: dict[str, list] = {}
    for fn in pkg.fns.values():
        for ref, line, _held in fn.calls:
            tgt = _resolve(pkg, fn, ref)
            if tgt is not None:
                edges.setdefault(fn.qual, []).append((tgt, line))

    while work:
        qual = work.pop()
        fn = pkg.fns[qual]
        for tgt, line in edges.get(qual, ()):
            callee = pkg.fns[tgt]
            if callee.blocking is not None:
                continue  # blocker leaf: checked, never entered
            if callee.is_async:
                continue  # calling a coroutine fn just builds the coro
            if callee.affinity and callee.affinity[0] in ("loop",
                                                          "ticker"):
                # declared affinity wins; crossings are reported as
                # CROSS-AFFINITY instead of cascading contexts through
                continue
            grew = False
            for ctx in fn.contexts:
                if ctx not in callee.contexts:
                    callee.contexts.add(ctx)
                    parent.setdefault((tgt, ctx), (qual, line))
                    grew = True
            if grew:
                work.append(tgt)
    return parent, edges


def _witness(pkg: _Package, parent: dict, qual: str, ctx: str) -> str:
    chain = [qual]
    seen = {qual}
    cur = qual
    while (cur, ctx) in parent:
        cur, _line = parent[(cur, ctx)]
        if cur in seen:
            break
        seen.add(cur)
        chain.append(cur)
    chain.reverse()
    root = pkg.fns.get(chain[0])
    seed_why = root.seeds.get(ctx, "") if root else ""
    path = " -> ".join(chain)
    return f"[{ctx}; {seed_why}] {path}" if seed_why else \
        f"[{ctx}] {path}"


# --------------------------------------------------------------- checks

def _check(pkg: _Package, parent: dict, edges: dict) -> list[Violation]:
    out: list[Violation] = []

    # CROSS-AFFINITY --------------------------------------------------
    for fn in pkg.fns.values():
        for tgt, line in edges.get(fn.qual, ()):
            callee = pkg.fns[tgt]
            if not (callee.affinity and callee.affinity[0] == "loop"):
                continue
            for ctx in sorted(fn.contexts):
                if _loopish(ctx) or _concrete(ctx) is None:
                    continue
                out.append(Violation(
                    pass_name="concurrency", path=fn.path, line=line,
                    message=f"CROSS-AFFINITY: {callee.qual} is "
                            f"@loop_only({callee.affinity[1]!r}) but is "
                            f"called from {ctx} — "
                            f"{_witness(pkg, parent, fn.qual, ctx)}",
                    suggestion="route through a loopback seam "
                               f"({', '.join(LOOPBACK_SEAMS)}) or "
                               "call_soon_threadsafe, or waive in "
                               "tools/fluidlint/concurrency_waivers.py"))

    # BLOCKING-ON-LOOP ------------------------------------------------
    for fn in pkg.fns.values():
        loop_ctxs = sorted(c for c in fn.contexts if _loopish(c))
        if not loop_ctxs:
            continue
        ctx = loop_ctxs[0]
        for line, what, why in fn.blocker_hits:
            out.append(Violation(
                pass_name="concurrency", path=fn.path, line=line,
                message=f"BLOCKING-ON-LOOP: {what} in {fn.qual} is "
                        f"reachable from the event loop ({why}) — "
                        f"{_witness(pkg, parent, fn.qual, ctx)}",
                suggestion="move it behind run_in_executor / a drain "
                           "seam, or waive with a justification"))
        for tgt, line in edges.get(fn.qual, ()):
            callee = pkg.fns[tgt]
            if callee.blocking is not None:
                out.append(Violation(
                    pass_name="concurrency", path=fn.path, line=line,
                    message=f"BLOCKING-ON-LOOP: {fn.qual} calls "
                            f"@blocking {callee.qual} "
                            f"({callee.blocking}) on the event loop — "
                            f"{_witness(pkg, parent, fn.qual, ctx)}",
                    suggestion="move it behind run_in_executor / a "
                               "drain seam, or waive with a "
                               "justification"))

    # UNFENCED-SHARED-STATE -------------------------------------------
    shared: dict[tuple, list] = {}
    for fn in pkg.fns.values():
        if fn.name in ("__init__", "__post_init__"):
            continue
        if fn.affinity and fn.affinity[0] == "any":
            continue  # the author asserts internal synchronization
        ctxs = {_concrete(c) for c in fn.contexts}
        ctxs.discard(None)
        if not ctxs:
            continue
        for attr, line, fences in fn.writes:
            shared.setdefault((fn.module, fn.cls, attr), []).append(
                (fn, line, fences, frozenset(ctxs)))
    for (module, cls, attr), writers in sorted(
            shared.items(), key=lambda kv: str(kv[0])):
        if cls is None:
            continue
        all_ctxs = set()
        for _fn, _line, _fences, ctxs in writers:
            all_ctxs |= ctxs
        if len(all_ctxs) < 2:
            continue
        common = None
        for _fn, _line, fences, _ctxs in writers:
            common = set(fences) if common is None else common & fences
        if common:
            continue  # every write holds a shared fence
        fn0, line0 = writers[0][0], writers[0][1]
        who = ", ".join(sorted({
            f"{w[0].name} ({'/'.join(sorted(w[3]))})" for w in writers}))
        out.append(Violation(
            pass_name="concurrency", path=fn0.path, line=line0,
            message=f"UNFENCED-SHARED-STATE: {cls}.{attr} is written "
                    f"from {len(all_ctxs)} contexts "
                    f"({', '.join(sorted(all_ctxs))}) with no common "
                    f"lock fence — writers: {who}",
            suggestion="guard every write with one shared lock "
                       "(`with self._lock:` / @holds_lock), or waive "
                       "as documented single-writer"))

    # LOCK-ORDER ------------------------------------------------------
    def order_check(fn, line, held, acquiring):
        for h in held:
            if LOCK_RANK[h] > LOCK_RANK[acquiring]:
                out.append(Violation(
                    pass_name="concurrency", path=fn.path, line=line,
                    message=f"LOCK-ORDER: {fn.qual} acquires "
                            f"'{acquiring}' while holding '{h}' — the "
                            "global order is "
                            f"{' -> '.join(LOCK_ORDER)}",
                    suggestion="restructure so acquisition follows the "
                               "order table (tools/lint.sh --fix-order "
                               "prints it)"))

    for fn in pkg.fns.values():
        for name in fn.holds:
            # dotted names ("MetricsRegistry._lock") are instance-lock
            # fences — REQUIRES()-style caller preconditions, not part
            # of the global order; bare names must be registered
            if "." not in name and name not in LOCK_RANK:
                out.append(Violation(
                    pass_name="concurrency", path=fn.path,
                    line=fn.lineno,
                    message=f"@holds_lock({name!r}) on {fn.qual} names "
                            "a lock missing from the global order "
                            "table",
                    suggestion="register it in LOCK_ORDER in "
                               "tools/fluidlint/registries.py (order "
                               "matters: outermost first)"))
        for lock, line, held in fn.acquires:
            order_check(fn, line, held, lock)
        for ref, line, held in fn.calls:
            if not held:
                continue
            tgt = _resolve(pkg, fn, ref)
            if tgt is None:
                continue
            for lock in pkg.fns[tgt].holds:
                if lock in LOCK_RANK:
                    order_check(fn, line, held, lock)
    return out


# ------------------------------------------------------------- waivers

def _apply_waivers(violations, waivers, waived_out: Optional[list]):
    kept = []
    used = [False] * len(waivers)
    for v in violations:
        hit = None
        for i, w in enumerate(waivers):
            rule, qual, detail, why = w
            if not v.message.startswith(rule + ":"):
                continue
            if qual not in v.message:
                continue
            if detail and detail not in v.message:
                continue
            hit = i
            break
        if hit is None:
            kept.append(v)
        else:
            used[hit] = True
            if waived_out is not None:
                rule, qual, detail, why = waivers[hit]
                waived_out.append(
                    f"waived [{rule}] {qual}"
                    + (f" ({detail})" if detail else "")
                    + f" -- {why}")
    return kept, used


def check_concurrency(repo_root: Optional[str] = None,
                      roots: tuple = PACKAGE_ROOTS,
                      waivers: Optional[tuple] = None,
                      waived_out: Optional[list] = None
                      ) -> list[Violation]:
    """Run the whole-package pass. ``waivers`` defaults to the
    checked-in table; pass ``()`` to see everything (the self-tests
    do). An unused waiver is itself a violation — a waiver that no
    longer matches anything is stale documentation."""
    repo_root = repo_root or _repo_root()
    if waivers is None:
        from .concurrency_waivers import WAIVERS
        waivers = WAIVERS
    pkg = _Package()
    for r in roots:
        root = os.path.join(repo_root, r)
        if not os.path.isdir(root):
            continue
        root_pkg = os.path.basename(os.path.normpath(root))
        for path in _py_files(root):
            rel = os.path.relpath(path, repo_root)
            mod_rel = os.path.relpath(path, root)[:-3]
            is_pkg = os.path.basename(path) == "__init__.py"
            parts = [p for p in mod_rel.split(os.sep)
                     if p != "__init__"]
            module = ".".join(parts) if parts else root_pkg
            _collect_module(pkg, path, rel, module, root_pkg, is_pkg)
    parent, edges = _propagate(pkg)
    violations = _check(pkg, parent, edges)
    kept, used = _apply_waivers(violations, waivers, waived_out)
    for w, u in zip(waivers, used):
        if not u:
            rule, qual, detail, why = w
            kept.append(Violation(
                pass_name="concurrency",
                path=os.path.join("tools", "fluidlint",
                                  "concurrency_waivers.py"),
                line=1,
                message=f"stale waiver: [{rule}] {qual} "
                        f"({detail or 'any'}) matches no finding",
                suggestion="delete it — the exception it documented is "
                           "gone"))
    return kept


def _repo_root() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
