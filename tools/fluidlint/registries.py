"""The frozen registries, in one manifest.

Each fluidlint pass used to hand-roll its own loader for the contract
table it checks against — the journal pass parsed ``KINDS`` out of
``obs/journal.py``, the metric pass carried ``LOCKED_FAMILIES`` inline,
the wire pass knew the codec files but not the frame-id inventory, and
the hop catalog lived only in ``utils/telemetry.py``. This module is
the single home: every registry that is a WIRE or ALERT contract (ids
and names other builds/dashboards key on) loads or lives here, so a
pass that needs one imports it instead of re-parsing, and a human
auditing "what is frozen in this tree" reads one file.

Registries:

- :func:`load_journal_kinds` — the audit journal's closed kind
  vocabulary (``obs/journal.py`` ``KINDS``; must stay a pure literal).
- :func:`load_hops` — the hop catalog (``utils/telemetry.py``
  ``HOPS``): ids 0–8 are FROZEN wire values stamped into trace tails.
- :func:`load_span_catalogs` — the span and sample catalogs
  (``utils/telemetry.py`` ``SPANS`` / ``SAMPLES``): the closed sets of
  names ``span(...)`` may time and ``sample(...)`` may keep.
- :data:`LOCKED_FAMILIES` — metric families whose exact member sets
  are alert-surface contracts (moved here from metrics_check).
- :func:`load_frame_types` — the binary codec's ``FT_*`` frame ids
  (``protocol/binwire.py``); ids are frozen wire values.
- :data:`FT_CODECS` — frame type → (encoder, decoder) pairing: every
  frame id on the wire must have both halves, checked by wire_check.
- :data:`LOCK_ORDER` / :data:`LOCK_DOC` — the single global lock
  acquisition order the concurrency pass enforces (outermost first).
"""

from __future__ import annotations

import ast
import os
from typing import Optional

#: Declaring modules (repo-relative).
JOURNAL_KINDS_HOME = os.path.join("fluidframework_tpu", "obs",
                                  "journal.py")
HOPS_HOME = os.path.join("fluidframework_tpu", "utils", "telemetry.py")
BINWIRE_HOME = os.path.join("fluidframework_tpu", "protocol",
                            "binwire.py")


def _repo_root() -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))


def _module_literal(path: str, name: str):
    """The value of a module-level ``name = <pure literal>`` assignment,
    or None when missing / not a literal."""
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return None
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == name):
            try:
                return ast.literal_eval(node.value)
            except ValueError:
                return None
    return None


# ---------------------------------------------------------- journal kinds

def load_journal_kinds(repo_root: Optional[str] = None
                       ) -> Optional[frozenset]:
    """The declared journal kind set, or None when the KINDS table is
    missing or not a pure literal (the journal pass reports that)."""
    repo_root = repo_root or _repo_root()
    kinds = _module_literal(
        os.path.join(repo_root, JOURNAL_KINDS_HOME), "KINDS")
    if isinstance(kinds, dict):
        return frozenset(kinds)
    return None


# ------------------------------------------------------------ hop catalog

def load_hops(repo_root: Optional[str] = None) -> Optional[tuple]:
    """The hop catalog as ((service, action, short), ...) — index IS
    the frozen wire id. None when HOPS is missing or not a literal."""
    repo_root = repo_root or _repo_root()
    hops = _module_literal(os.path.join(repo_root, HOPS_HOME), "HOPS")
    if (isinstance(hops, tuple)
            and all(isinstance(h, tuple) and len(h) == 3 for h in hops)):
        return hops
    return None


# ----------------------------------------------------------- span catalog

def load_span_catalogs(repo_root: Optional[str] = None
                       ) -> Optional[dict]:
    """{"SPANS": names, "SAMPLES": names}, or None when either table is
    missing or not a pure dict literal (the metric-name pass reports
    that)."""
    path = os.path.join(repo_root or _repo_root(), HOPS_HOME)
    out = {}
    for name in ("SPANS", "SAMPLES"):
        table = _module_literal(path, name)
        if not isinstance(table, dict):
            return None
        out[name] = frozenset(table)
    return out


# -------------------------------------------------------- metric families

#: prefix -> exact member set. These families are overload-control
#: alert surfaces (SLO dashboards, the overload bench's gates, the
#: noisy-neighbor scenario); a name under one of these prefixes that
#: is not in the set is either a typo or an unreviewed contract change.
LOCKED_FAMILIES = {
    "obs.slo.": frozenset({"obs.slo.state", "obs.slo.violations"}),
    # the live health plane (obs/probe.py + obs/health.py): the
    # net-smoke health gate, `admin health --fleet`, and the rolling-
    # upgrade wait_healthy primitive all key on these exact names —
    # probe.ms{door} is the canary's per-door latency window,
    # engine.state is the per-component ok/degraded/critical gauge
    "health.": frozenset({"health.probe.ms", "health.probe.failures",
                          "health.engine.state"}),
    "net.admission.": frozenset({"net.admission.shed",
                                 "net.admission.delayed"}),
    # the snapshot fast-boot plane: the net-smoke catch-up gate, the
    # join-storm bench, and the chaos soak all key on these exact names;
    # boot.part.* witness the fleet cold-start contract (lazy == every
    # existing doc booted O(snapshot+tail), full_replay == the count the
    # cold-storm bench and net_smoke gate assert ZERO) and
    # boot.parked.retries is the driver's storm-admission retry lane
    # (service/rehydrate.py, service/local_orderer.py)
    "boot.": frozenset({"boot.snapshot.used", "boot.snapshot.fallback",
                        "boot.snapshot.reanchor", "boot.backfill.bounded",
                        "boot.backfill.full", "boot.chunks.fetched",
                        "boot.chunks.cached",
                        "boot.part.lazy", "boot.part.full_replay",
                        "boot.part.fresh", "boot.part.parked",
                        "boot.parked.retries"}),
    # the topology spec / fleet launcher (service/topology.py): the
    # cold-storm bench and the coldstart chaos drill key on these to
    # prove restarts really went through the one declarative spec
    # topology.fleet.host_kills / host_starts witness whole-host-group
    # chaos (chaos/multihost.py kill -9's one host's process group and
    # resurrects it through the same spec)
    "topology.": frozenset({"topology.fleet.starts",
                            "topology.fleet.restarts",
                            "topology.fleet.kills",
                            "topology.fleet.host_kills",
                            "topology.fleet.host_starts",
                            "topology.core.spawns"}),
    "storage.snapshot.": frozenset({"storage.snapshot.encodes",
                                    "storage.snapshot.cache_hits",
                                    "storage.snapshot.served",
                                    "storage.snapshot.legacy_tree",
                                    "storage.snapshot.chunks_written",
                                    "storage.snapshot.chunks_reused"}),
    # the device-dispatch pipeline: MULTICHIP's smoke gate counter-
    # asserts overlap_ratio, profile_applier prints the stage/execute
    # split, and the r7+ plateau analysis keys on these exact names
    # (service/tpu_applier.py)
    "applier.": frozenset({"applier.kernel.recompiled",
                           "applier.stage.seconds",
                           "applier.stage.bytes",
                           "applier.stage.overlap_ratio",
                           "applier.dispatch.seconds",
                           # the local farm's waves by step (tile or
                           # full) and the rows they occupied / covered
                           "applier.wave.tiled",
                           "applier.wave.full",
                           "applier.wave.rows",
                           "applier.wave.rows_padded",
                           # the long tier's, on the stage's counters
                           # (service/stage_runner.py ApplierStage._count)
                           "applier.promotions",
                           "applier.long.dispatches",
                           "applier.long.slots_peak",
                           # the grids', as the long tier's
                           "applier.grid.dispatches",
                           "applier.grid.cells_set",
                           "applier.grid.dead_writes",
                           "applier.grid.handles_peak",
                           "applier.grid.escalations"}),
    # the placement control plane: the net-smoke migration gate, the
    # admin CLI, and the chaos migration campaign key on these exact
    # names (service/placement_plane.py); placement.heat.* are the
    # rebalancer's windowed per-partition load series and
    # placement.rebalance.* count the self-driving loop's decisions —
    # the storm bench's flap-free gate keys on them.
    # placement.table.* are the networked table plane's client-side
    # counters (service/table_client.py): the multi-host bench publishes
    # cache_hits/rpc_reads as the coherence-protocol hit rate, and the
    # doctor flags stale_rejections > 0 as a fenced zombie writer;
    # heat.scrape_timeouts counts peers dropped from a fleet heat
    # fan-out by the per-peer dial deadline (service/rebalancer.py)
    "placement.": frozenset({"placement.epoch.bumps",
                             "placement.epoch.stale_nacks",
                             "placement.cache.hits",
                             "placement.cache.refreshes",
                             "placement.cache.invalidations",
                             "placement.submits.redirected",
                             "placement.migration.fences",
                             "placement.migration.committed",
                             "placement.migration.failed",
                             "placement.migration.adopted",
                             "placement.heat.ops",
                             "placement.heat.bytes",
                             "placement.heat.scrape_timeouts",
                             "placement.table.rpc_reads",
                             "placement.table.rpc_writes",
                             "placement.table.cache_hits",
                             "placement.table.stale_rejections",
                             "placement.rebalance.ticks",
                             "placement.rebalance.plans",
                             "placement.rebalance.migrations_issued",
                             "placement.rebalance.suppressed_hysteresis",
                             "placement.rebalance.suppressed_budget"}),
    # the read-scale fan-out tier (ISSUE 12): the net-smoke relay gate
    # counter-asserts splices > 0 and encodes == 0 above the first
    # gateway level, and the read-storm bench keys on upstream bytes —
    # these exact names are the relay tree's perf contract
    # (service/gateway.py). NOTE: "fanout." does not collide with the
    # front end's "net.fanout.*" cache counters — prefixes match from
    # the name's start.
    # fanout.upstream.same_host / cross_host split route resolutions by
    # host locality (ISSUE 19): the multi-host bench's locality hit
    # rate is same_host / (same_host + cross_host)
    "fanout.": frozenset({"fanout.relay.splices",
                          "fanout.relay.encodes",
                          "fanout.upstream.frames",
                          "fanout.upstream.bytes",
                          "fanout.upstream.same_host",
                          "fanout.upstream.cross_host"}),
    # the ephemeral presence lane: the soak's drop/dup rules prove loss
    # is invisible BECAUSE coalescing happens, which only these names
    # witness (service/presence.py)
    "presence.": frozenset({"presence.lane.signals",
                            "presence.lane.coalesced",
                            "presence.lane.flushes",
                            "presence.lane.delivered"}),
    "session.readonly.": frozenset({"session.readonly.connects"}),
    # the control-plane audit journal's own health counters: the bench
    # journal A/B and the doctor's write-error triage key on these
    # exact names (obs/journal.py)
    "obs.journal.": frozenset({"obs.journal.entries",
                               "obs.journal.bytes",
                               "obs.journal.errors",
                               "obs.journal.rotations"}),
    # the doc history plane: the net-smoke history gate and the
    # fork-storm bench counter-assert fork boots / replay reads /
    # integrate ops on these exact names (service/history_plane.py;
    # history.replay.legacy is the replay tool's whole-log-replay
    # deprecation gauge, replay/tool.py)
    "history.": frozenset({"history.commit.records",
                           "history.fork.boots",
                           "history.fork.tail_ops",
                           "history.replay.reads",
                           "history.replay.log_scans",
                           "history.replay.legacy",
                           "history.integrate.sessions",
                           "history.integrate.ops",
                           "history.gc.scanned",
                           "history.gc.pinned",
                           "history.gc.deleted",
                           "history.ref.recovered"}),
}


# ----------------------------------------------------------- frame types

def load_frame_types(repo_root: Optional[str] = None) -> dict:
    """Module-level ``FT_* = <int>`` assignments from the binary codec:
    {name: (id, lineno)}. Ids are frozen wire values."""
    repo_root = repo_root or _repo_root()
    path = os.path.join(repo_root, BINWIRE_HOME)
    out: dict = {}
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return out
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id.startswith("FT_")
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, int)):
            out[node.targets[0].id] = (node.value.value, node.lineno)
    return out


#: frame type -> (encoder fn, decoder fn) in protocol/binwire.py. Both
#: halves must exist for every id on the wire: a frame a peer can send
#: that this build cannot read (or the reverse) is version skew baked
#: into one binary. wire_check asserts the manifest covers every FT_*
#: assignment and that both named functions are defined.
FT_CODECS = {
    "FT_SUBMIT": ("encode_submit", "decode_submit"),
    "FT_OPS": ("encode_ops", "decode_ops"),
    "FT_FSUBMIT": ("encode_submit", "decode_submit"),
    "FT_FOPS": ("encode_ops", "decode_ops"),
    "FT_COLS_SUBMIT": ("encode_submit_columns", "decode_submit_columns"),
    "FT_COLS_FSUBMIT": ("encode_submit_columns",
                        "decode_submit_columns"),
    "FT_COLS_OPS": ("stamp_cols_ops", "decode_cols_ops"),
    "FT_COLS_FOPS": ("stamp_cols_ops", "decode_cols_ops"),
    "FT_COLS_DELTAS": ("cols_deltas_body", "read_cols_deltas"),
    "FT_COLS_SNAP": ("snap_chunk_body", "read_snap_chunk"),
    "FT_PRESENCE": ("encode_presence", "decode_presence"),
    "FT_FPRESENCE": ("encode_presence", "decode_presence"),
    "FT_HISTORY": ("encode_history_commit", "decode_history_commit"),
}


# ------------------------------------------------------------- lock order

#: THE global lock acquisition order, outermost first. A function that
#: acquires a later lock may not then acquire an earlier one — the
#: concurrency pass enforces this over `with` nesting and @holds_lock
#: annotations, so an epoch-table↔lease deadlock cannot land silently
#: as multi-host fleet ops add acquirers. `tools/lint.sh --fix-order`
#: prints this table.
LOCK_ORDER = (
    "epoch_table_flock",      # placement_plane._flock(table.lock)
    "partition_claim_flock",  # placement.PlacementDir._lock(k)
    "journal_lock",           # obs.journal.Journal._lock
)

#: lock name -> what it guards (printed by --fix-order and the report).
LOCK_DOC = {
    "epoch_table_flock": "the fleet epoch table file "
                         "(service/placement_plane.py _flock)",
    "partition_claim_flock": "per-partition lease files "
                             "(service/placement.py PlacementDir._lock)",
    "journal_lock": "the audit journal's append stream "
                    "(obs/journal.py Journal._lock)",
}

LOCK_RANK = {name: i for i, name in enumerate(LOCK_ORDER)}
