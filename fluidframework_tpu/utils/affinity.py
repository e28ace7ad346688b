"""Execution-context markers for the concurrency contract checker.

Every hard-way bug in PRs 10–13 was a thread-discipline violation, not
a logic error: CPU donation silently serializing dispatch, staging
refills racing in-flight executions, ``tier_counters`` weakrefs dying
under the ticker thread, migrations that are only sound on the core's
event loop. These decorators make the discipline *visible* so
``tools/fluidlint/concurrency_check.py`` can enforce it statically
(RacerD / Clang ``-Wthread-safety`` style: annotate the boundaries,
propagate contexts along the call graph, flag the crossings).

They are pure markers — at runtime each costs ONE attribute assignment
at import time and nothing per call (the function object is returned
unwrapped). The checker reads them from the AST, so even un-imported
fixture trees are checkable.

Vocabulary (the context strings the checker propagates):

- ``@loop_only("core")`` — must only ever run on the named event-loop
  thread. The front end's pipeline, admission, presence, and the
  migration engine are ``loop_only("core")``: single-threadedness IS
  their locking discipline.
- ``@ticker_thread("slo")`` — runs on the named daemon ticker thread
  (SloEngine, Rebalancer, the applier worker). Also the right marker
  for callbacks *handed to* a ticker (the rebalancer's actuate seam).
- ``@any_thread`` — safe from any context; the function synchronizes
  internally (the journal's lock-guarded ``emit``).
- ``@holds_lock("epoch_table_flock")`` — acquires and holds the named
  lock for its body. Feeds the LOCK-ORDER rule (acquisitions must
  follow the single global order table) and fences shared-state writes.
- ``@blocking("...")`` — performs blocking I/O (socket round-trip,
  flock, mmap flush). A call to a ``blocking`` function reachable from
  an event-loop context is a BLOCKING-ON-LOOP violation unless waived.
"""

from __future__ import annotations

__all__ = ["loop_only", "ticker_thread", "any_thread", "holds_lock",
           "blocking"]


def loop_only(loop_name: str = "core"):
    """This function must only run on the named event-loop thread."""
    def mark(fn):
        fn.__affinity__ = ("loop", loop_name)
        return fn
    return mark


def ticker_thread(ticker_name: str):
    """This function runs on (or is a callback for) the named daemon
    ticker thread."""
    def mark(fn):
        fn.__affinity__ = ("ticker", ticker_name)
        return fn
    return mark


def any_thread(fn):
    """Safe from any context — the function synchronizes internally."""
    fn.__affinity__ = ("any", "")
    return fn


def holds_lock(lock_name: str):
    """The function acquires and holds the named registry lock for its
    body (see tools/fluidlint/registries.py LOCK_ORDER)."""
    def mark(fn):
        held = list(getattr(fn, "__holds_locks__", ()))
        held.append(lock_name)
        fn.__holds_locks__ = tuple(held)
        return fn
    return mark


def blocking(why: str):
    """The function performs blocking I/O; ``why`` names the operation
    and the PR that made it load-bearing."""
    def mark(fn):
        fn.__blocking__ = why
        return fn
    return mark
