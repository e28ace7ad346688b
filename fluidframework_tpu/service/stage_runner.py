"""Per-stage service processes over the shared durable log.

Ref: the reference deploys each pipeline lambda as its own service
process connected only by the Kafka log — alfred/deli/scribe/…
each have a www.ts entrypoint run by the kafka-service runner
(server/routerlicious/packages/routerlicious/src/*/www.ts,
lambdas-driver/src/kafka-service/runner.ts:13, docker-compose.yml).

Here the shared medium is the native C++ op log (native/oplog.cpp): the
CORE process (front_end.py with ``--log-dir``) is the single writer of
the rawops/deltas topics and flushes appends into the page cache; each
stage process opens the same directory READ-ONLY and tails it
(DurableLog.poll). Stage → core communication rides the stage's own
writable log directory (its "backchannel"), which the core polls — every
topic keeps exactly one writer, so no cross-process file locking exists
anywhere.

Stages:

- ``scribe``  — the summary validator/acker (ScribeLambda) out of
  process. Consumes deltas + upload announcements; emits summary
  ack/nack raw messages, version commits, and retention advances on the
  backchannel. Checkpoints its protocol replica + offsets to its own
  log; kill -9 and restart resumes from the checkpoint (deltas replay is
  idempotent by sequence number).
- ``applier`` — the TPU device farm (TpuDocumentApplier) out of
  process: the deli/broadcast hot path never shares a GIL with device
  work. Consumes deltas chanops of each doc's text channel and, where
  the applier holds grids, of its SharedMatrix channel once an attach
  names one (a grid, on the same device); after every turn that
  consumed records
  it checkpoints the docs that moved (a delta on the farm's base,
  tpu_applier.ApplierCheckpointer) and reports their applied seqs on
  its backchannel as status records.

Deployment:

    python -m fluidframework_tpu.service.stage_runner \
        --stage scribe --log-dir LOG --state-dir STATE

The core consumes STATE with ``front_end --consume-backchannel STATE``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
from typing import Optional

from ..obs.metrics import tier_counters
from ..utils.telemetry import (sample, span, tracing_armed, tracing_start,
                               tracing_stop)
from .core import InMemoryDb, summary_versions_collection
from .durable_log import DurableLog, log_channel_stream
from .synthetic import GRID_CHANNEL, chanop_of

BACKCHANNEL_TOPIC = "backchannel"
POLL_INTERVAL_S = 0.002


def _doc_of(topic: str) -> tuple[str, str]:
    _, tenant, doc = topic.split("/", 2)
    return tenant, doc


def attaches_grid(m, ds_id: str) -> bool:
    """Whether a sequenced message attaches a SharedMatrix channel named
    ``GRID_CHANNEL`` in data store ``ds_id``."""
    env = m.contents
    if type(env) is not dict or env.get("kind") != "chanop" \
            or env.get("address") != ds_id:
        return False
    inner = env.get("contents")
    return (type(inner) is dict and inner.get("address") == GRID_CHANNEL
            and (inner.get("attach") or {}).get("type") == "shared-matrix")


def doc_partition(tenant: str, doc: str, n_partitions: int) -> int:
    """Stable doc → partition map (ref: the Kafka partition-by-docId
    routing, lambdas-driver document-router). md5, NOT hash(): python
    randomizes hash() per process, and every stage process must agree."""
    import hashlib

    digest = hashlib.md5(f"{tenant}/{doc}".encode()).digest()
    return int.from_bytes(digest[:4], "big") % n_partitions


class _StageHostBase:
    """Discovery + poll/drain/checkpoint loop shared by the stages."""

    #: deltas topics are the stage input; uploads only matter to scribe
    topic_prefixes = ("deltas/",)

    #: chaos seam (fluidframework_tpu/chaos): crash-window faults. The
    #: plane raises SimulatedCrash from inside the checkpoint sequence —
    #: between consume and farm save ("stage.pre_checkpoint") or between
    #: farm save and the offset/emit records ("stage.post_checkpoint") —
    #: the two windows whose replay/idempotency story must hold on a real
    #: kill -9. None = disarmed, one branch per checkpoint.
    fault_plane = None

    #: set to drain-stop (the applier's SIGINT): run_forever finishes
    #: its pass, checkpoints and returns
    stopping = False
    #: armed tracing: "start" or "stop", set by a signal handler and
    #: acted on by run_forever between loop turns — never inside the
    #: handler, which can land while a span holds the window's lock
    trace_request: Optional[str] = None

    def _fault(self, point: str, **ctx) -> None:
        if self.fault_plane is not None:
            self.fault_plane(point, stage=type(self).__name__, **ctx)

    def __init__(self, log_dir: str, state_dir: str,
                 partition: Optional[tuple] = None):
        self.shared = DurableLog(log_dir, readonly=True)
        self.state = DurableLog(state_dir)
        # (k, n): this process owns docs with doc_partition(...) == k —
        # N stage processes split the doc space; a redeploy with a
        # different split MOVES docs between processes (the new owner
        # resumes from its checkpoints, or replays from 0 for a doc it
        # never owned)
        self.partition = partition
        self._known: set[str] = set()
        self.counters = tier_counters("stage")

    def _owns(self, topic: str) -> bool:
        if self.partition is None:
            return True
        tenant, doc = _doc_of(topic)
        k, n = self.partition
        return doc_partition(tenant, doc, n) == k

    # ------------------------------------------------------------- plumbing

    def emit(self, record: dict) -> None:
        self.state.append(BACKCHANNEL_TOPIC, record)

    def _cp_topic(self, tenant: str, doc: str) -> str:
        return f"cp/{tenant}/{doc}"

    def load_checkpoint(self, tenant: str, doc: str) -> Optional[dict]:
        topic = self._cp_topic(tenant, doc)
        n = self.state.length(topic)
        return self.state.read(topic, n - 1) if n > 0 else None

    def save_checkpoint(self, tenant: str, doc: str, state: dict) -> None:
        self.state.append(self._cp_topic(tenant, doc), state)

    def discover(self) -> None:
        for prefix in self.topic_prefixes:
            for topic in self.shared.list_topics(prefix):
                if topic not in self._known:
                    self._known.add(topic)
                    if self._owns(topic):
                        self.attach(topic)

    # -------------------------------------------------- deterministic ctl
    # Cross-process stepping (VERDICT r4 #9 — the OpProcessingController
    # role, opProcessingController.ts:16, extended across the process
    # boundary): a controller writes ``<state_dir>/ctl.json`` with
    # {"mode": "pause"|"run", "steps": N} and this stage consumes AT
    # MOST N records total while paused — so a composition bug
    # reproduces op-by-op, each step observable through the backchannel.

    def _read_ctl(self) -> None:
        path = os.path.join(self.state.directory, "ctl.json")
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            return
        if mtime == self._ctl_mtime:
            return
        self._ctl_mtime = mtime
        try:
            with open(path) as f:
                self._ctl = json.load(f)
        except (OSError, ValueError):
            pass

    def _step_once(self) -> bool:
        """Deliver exactly ONE pending record (first lagging topic in
        subscription order). Returns False when fully drained."""
        for topic in list(self.shared._order):
            if self.shared.step(topic):
                return True
        return False

    def run_forever(self) -> None:
        """Tail, drain and checkpoint until :attr:`stopping` is set, then
        checkpoint once more."""
        last_discover = 0.0
        self._ctl = {"mode": "run"}
        self._ctl_mtime = None
        self._steps_done = 0
        while not self.stopping:
            if self.trace_request is not None:
                self._serve_trace_request()
            now = time.monotonic()
            if now - last_discover >= 0.25:  # listdir is not free at 2ms
                last_discover = now
                self.discover()
                self._read_ctl()
            with span("stage.poll"):
                moved = self.shared.poll()
            if self._ctl.get("mode") != "pause":
                # leaving (or never entering) a pause episode resets the
                # step ledger: each pause session's budget counts from 0,
                # not from the lifetime total of earlier sessions
                self._steps_done = 0
            if self._ctl.get("mode") == "pause":
                self._read_ctl()
                budget = int(self._ctl.get("steps", 0))
                stepped = False
                while self._steps_done < budget and self._step_once():
                    self._steps_done += 1
                    stepped = True
                if stepped:
                    self.checkpoint()
                    self.state.flush()
                time.sleep(0.01)
                continue
            if moved:
                with span("stage.drain"):
                    self.counters.inc("stage.records.drained",
                                      self.shared.drain())
            if self._checkpoint_due():
                self.checkpoint()
            with span("stage.state_flush"):
                self.state.flush()
            if not moved:
                time.sleep(POLL_INTERVAL_S)
        self.checkpoint(final=True)
        self.state.flush()

    def run_once(self) -> bool:
        """ONE deterministic iteration of the run_forever loop body:
        discover, poll, drain, checkpoint, flush. Lets a driver (the
        chaos soak, a test) step a stage in-process and catch a
        SimulatedCrash exactly at the armed window. Returns whether the
        poll found new records."""
        self.discover()
        moved = self.shared.poll()
        if moved:
            self.shared.drain()
        self.checkpoint()
        self.state.flush()
        return moved

    # ------------------------------------------------------------ per-stage

    def _serve_trace_request(self) -> None:
        """Act on :attr:`trace_request`; a stage that traces overrides
        this."""
        self.trace_request = None

    def attach(self, topic: str) -> None:
        raise NotImplementedError

    def _checkpoint_due(self) -> bool:
        """Whether this loop turn ends with a checkpoint: each stage
        keeps its own cadence."""
        raise NotImplementedError

    def checkpoint(self, final: bool = False) -> None:
        """Save what was consumed; ``final`` is the drain-stop's."""


class ScribeStage(_StageHostBase):
    """ScribeLambda per doc, out of process (scribe/lambda.ts role)."""

    # uploads BEFORE deltas: an upload announcement always precedes its
    # SUMMARIZE op on disk (the core appends + flushes it during the
    # storage RPC, before the client can submit), and poll marks dirty /
    # drain delivers in SUBSCRIPTION order — so as long as the doc's
    # uploads topic is subscribed before its deltas topic, validation
    # never sees a summarize whose upload record it hasn't ingested.
    # attach() enforces that order by eagerly subscribing the uploads
    # topic when the deltas topic appears (the uploads topic is usually
    # created on disk much later — first upload — and discovery alone
    # would subscribe it AFTER deltas, racing any summarize that lands
    # in the same poll window as its upload: round-5 flake fix)
    topic_prefixes = ("uploads/", "deltas/")

    #: its checkpoint saves every scribe, so it runs on a timer
    checkpoint_every_s = 1.0

    def __init__(self, log_dir: str, state_dir: str,
                 partition=None):
        super().__init__(log_dir, state_dir, partition=partition)
        self.db = InMemoryDb()
        self.scribes: dict[str, object] = {}  # "tenant/doc" → ScribeLambda
        self._last_checkpoint = time.monotonic()

    def _scribe_for(self, tenant: str, doc: str):
        from .scribe import ScribeLambda

        key = f"{tenant}/{doc}"
        scribe = self.scribes.get(key)
        if scribe is None:
            cp = self.load_checkpoint(tenant, doc)

            def send_raw(raw, tenant=tenant, doc=doc):
                # summary ack/nack → core orders it into the stream
                self.emit({"kind": "raw", "tenant": tenant, "doc": doc,
                           "raw": raw})

            def persist_version(handle, version, tenant=tenant, doc=doc):
                self.emit({"kind": "version", "tenant": tenant, "doc": doc,
                           "handle": handle, "version": dict(version)})

            def on_committed(capture_seq, tenant=tenant, doc=doc):
                self.emit({"kind": "retention", "tenant": tenant,
                           "doc": doc, "capture_seq": capture_seq})

            scribe = self.scribes[key] = ScribeLambda(
                tenant, doc, self.db,
                send_to_deli=send_raw,
                checkpoint=cp["scribe"] if cp else None,
                on_summary_committed=on_committed,
                persist_version=persist_version,
            )
        return scribe

    def attach(self, topic: str) -> None:
        tenant, doc = _doc_of(topic)
        scribe = self._scribe_for(tenant, doc)
        if topic.startswith("deltas/"):
            # subscribe the doc's uploads topic FIRST (see class comment)
            up_topic = f"uploads/{tenant}/{doc}"
            if up_topic not in self._known:
                self._known.add(up_topic)
                self.attach(up_topic)
            cp = self.load_checkpoint(tenant, doc)
            start = cp["deltas_offset"] + 1 if cp else 0
            self.shared.subscribe(topic, scribe.handler, from_offset=start)
        else:  # uploads/: version records announced by the core

            def on_upload(message, col=summary_versions_collection(
                    tenant, doc)):
                rec = message.value
                self.db.upsert(col, rec["version_id"], dict(rec["record"]))

            self.shared.subscribe(topic, on_upload, from_offset=0)

    def _checkpoint_due(self) -> bool:
        now = time.monotonic()
        if now - self._last_checkpoint < self.checkpoint_every_s:
            return False
        self._last_checkpoint = now
        return True

    def checkpoint(self, final: bool = False) -> None:
        # crash window: records consumed, checkpoint not yet written —
        # a restart replays the window (scribe replay is seq-idempotent)
        self._fault("stage.pre_checkpoint")
        for key, scribe in self.scribes.items():
            tenant, doc = key.split("/", 1)
            self.save_checkpoint(tenant, doc, {
                "scribe": scribe.checkpoint_state(),
                "deltas_offset": scribe.last_offset,
            })


class ApplierStage(_StageHostBase):
    """TpuDocumentApplier out of process: device work off the core GIL.

    Its geometry is ``applier_max_docs`` x ``applier_max_slots`` from
    the config registry (``FLUID_TPU_APPLIER_MAX_DOCS`` etc. in the
    launcher's environment); a doc past ``applier_max_docs`` ends the
    process (:class:`..parallel.placement.PlacementFull`)."""

    #: armed tracing: where the span file goes, and whether the stage
    #: also runs the JAX profiler there over the window
    trace_dir: Optional[str] = None
    trace_profile = False

    def __init__(self, log_dir: str, state_dir: str,
                 ds_id: str = "default", channel_id: str = "text",
                 partition=None):
        super().__init__(log_dir, state_dir, partition=partition)
        from .tpu_applier import (ApplierCheckpointer, TpuDocumentApplier,
                                  load_applier_checkpoint)

        self.ds_id, self.channel_id = ds_id, channel_id
        ckpt = os.path.join(state_dir, "applier")
        if os.path.exists(ckpt + ".json"):
            self.applier = load_applier_checkpoint(ckpt)
        else:
            self.applier = TpuDocumentApplier()
        # an escalated doc rebuilds on the host oracle from the same
        # deltas topics this stage tails (read-only, durable before any
        # consumer sees a record)
        self.applier.set_replay_source(
            lambda t, d: log_channel_stream(self.shared, t, d,
                                            self.ds_id, self.channel_id))
        self.applier.set_grid_replay_source(
            lambda t, d: log_channel_stream(self.shared, t, d,
                                            self.ds_id, GRID_CHANNEL))
        # a base at start: the deltas that follow need one, and it drops
        # whatever delta files a crash left
        self._ckpt = ApplierCheckpointer(self.applier, ckpt)
        self._tallied: dict[str, int] = {}
        self._count(self._ckpt.base())
        self._offsets: dict[str, int] = {}
        # topics whose consumed offset moved since the last publish: the
        # only ones it writes offsets and "applied" records for
        self._moved: dict[str, None] = {}
        # highest sequence number CONSUMED per topic (the consumer-group
        # offset semantic): the stream tail includes messages the applier
        # skips (joins, summarize/ack, other channels), and "caught up"
        # must mean consumed-through-tail, not merely
        # last-applicable-op-applied — otherwise a stream ending in a
        # summary ack reads as forever lagging
        self._watermarks: dict[str, int] = {}
        # per topic: the deli wall stamp of the newest message consumed,
        # and the applied seq last published — "applied" records whose
        # seq advanced give the deli-to-applied lag
        self._stamps: dict[str, float] = {}
        self._published: dict[str, int] = {}

    def attach(self, topic: str) -> None:
        tenant, doc = _doc_of(topic)
        cp = self.load_checkpoint(tenant, doc)
        start = cp["offset"] + 1 if cp else 0
        if cp:
            # a restart publishes each doc it has a checkpoint for once: a
            # kill between a doc's offset checkpoint and its "applied"
            # record must not leave the record missing until the doc moves
            self._offsets[topic] = cp["offset"]
            self._moved[topic] = None

        def on_deltas(message, tenant=tenant, doc=doc, topic=topic):
            self._offsets[topic] = message.offset
            self._moved[topic] = None
            value = message.value
            abatch = value.get("abatch")
            if abatch is not None:
                self._watermarks[topic] = max(
                    self._watermarks.get(topic, 0), abatch.last_seq)
                self._stamps[topic] = abatch.timestamp
                if abatch.last_seq > self.applier.applied_seq(tenant, doc):
                    self.applier.ingest_array_batch(tenant, doc, abatch)
                return
            batch = value.get("boxcar")
            msgs = batch if batch is not None else [value["message"]]
            self._watermarks[topic] = max(
                self._watermarks.get(topic, 0),
                msgs[-1].sequence_number)
            self._stamps[topic] = msgs[-1].timestamp
            # replay idempotency: the farm checkpoint is saved BEFORE
            # the offset checkpoints, so a crash in between replays a
            # window of already-applied ops — skip by sequence number
            # (double-applying an insert would corrupt the doc)
            applied = self.applier.applied_seq(tenant, doc)
            pairs, grid_pairs = [], []
            for m in msgs:
                if m.sequence_number <= applied:
                    continue
                wire = chanop_of(m, self.ds_id, self.channel_id)
                if wire is not None:
                    pairs.append((m, wire))
                    continue
                if not self.applier.grids:
                    continue
                wire = chanop_of(m, self.ds_id, GRID_CHANNEL)
                if wire is not None:
                    grid_pairs.append((m, wire))
                elif attaches_grid(m, self.ds_id):
                    self.applier.attach_grid(tenant, doc)
            if pairs:
                self.applier.ingest_batch(tenant, doc, pairs)
            if grid_pairs:
                self.applier.ingest_grid_batch(tenant, doc, grid_pairs)

        self.shared.subscribe(topic, on_deltas, from_offset=start)

    def _serve_trace_request(self) -> None:
        """Start or stop the program's spans (utils/telemetry.py) over
        :attr:`trace_dir`, with the stage's counters and its shared
        log's (``storage.tail.*``, ``storage.segment.*``); with
        :attr:`trace_profile` the JAX profiler runs into the same
        directory over the window. A stop prints ``SPANS <path>``. A
        repeated request is a no-op."""
        import jax

        req, self.trace_request = self.trace_request, None
        if req == "start" and not tracing_armed():
            if self.trace_profile:
                jax.profiler.start_trace(self.trace_dir)
            tracing_start(self.trace_dir, "stage",
                          counters=(self.counters, self.shared.counters))
        elif req == "stop" and tracing_armed():
            path = tracing_stop()
            if self.trace_profile:
                jax.profiler.stop_trace()
            print(f"SPANS {path}", flush=True)

    @classmethod
    def serve(cls, log_dir: str, state_dir: str, partition=None,
              trace_dir: Optional[str] = None) -> None:
        """The applier process: hold the chip, print ``READY`` and a
        ``STAGE {...}`` line (device, kernel lane, geometry), run until
        SIGINT drain-stops it, then print ``STOPPED {...}``. A doc past
        ``applier_max_docs`` exits 1 naming the setting.

        With ``trace_dir``, SIGUSR1 starts the profiler and the spans
        and SIGUSR2 stops both, writes the spans' file beside the
        profile and prints ``SPANS <path>``, each at the loop's next
        turn."""
        from ..parallel.placement import PlacementFull
        from ..utils.chip import (CompileClock, init_compile_cache,
                                  peak_bytes_in_use, require_tpu)

        init_compile_cache()
        dev = require_tpu()
        clock = CompileClock()
        stage = cls(log_dir, state_dir, partition=partition)
        a = stage.applier
        # the farm's packed and tile steps, the long tier's step and
        # promotion, and the grids' cells step compile here, before READY
        a.warm_farm()
        a.warm_long_tier()
        a.warm_grids()
        signal.signal(signal.SIGINT,
                      lambda *_: setattr(stage, "stopping", True))
        if trace_dir:
            stage.trace_dir, stage.trace_profile = trace_dir, True
            signal.signal(signal.SIGUSR1,
                          lambda *_: setattr(stage, "trace_request", "start"))
            signal.signal(signal.SIGUSR2,
                          lambda *_: setattr(stage, "trace_request", "stop"))
        print("READY", flush=True)
        print("STAGE " + json.dumps({
            "platform": dev.platform, "device_kind": dev.device_kind,
            "kernel_lane": a.kernel_lane, "max_docs": a.max_docs,
            "max_slots": a.max_slots, "ops_per_dispatch": a.K,
            "long_docs": a.long_docs, "long_slots": a.long_slots,
            "long_kernel_lane": a.long_kernel_lane, "grids": a.grids,
            "grid_handles": a.grid_handles}), flush=True)
        try:
            stage.run_forever()
        except PlacementFull as e:
            sys.exit(f"applier stage: {e}: more docs than applier_max_docs="
                     f"{a.max_docs} (set FLUID_TPU_APPLIER_MAX_DOCS)")
        print("STOPPED " + json.dumps({
            "docs": len(a.placement.snapshot()["map"]),
            "ops_applied": a.ops_applied, "dispatches": a.dispatches,
            "waves_tiled": a.waves_tiled, "waves_full": a.waves_full,
            "wave_rows": a.wave_rows,
            "wave_rows_padded": a.wave_rows_padded,
            "host_escalations": a.host_escalations,
            "promotions": a.promotions, "long_dispatches": a.long_dispatches,
            "long_slots_peak": a.long_slots_peak,
            "grid_dispatches": a.grid_dispatches,
            "grid_cells_set": a.grid_cells_set,
            "grid_dead_writes": a.grid_dead_writes,
            "grid_handles_peak": a.grid_handles_peak,
            "grid_escalations": a.grid_escalations,
            "compile_s": clock.seconds, "compiles": clock.compiles,
            "peak_bytes_in_use": peak_bytes_in_use(dev)}),
            flush=True)

    def _checkpoint_due(self) -> bool:
        # every turn that consumed records (or, after a restart, has
        # docs to publish), so "applied" follows the drain; never idle
        return bool(self._moved)

    def checkpoint(self, final: bool = False) -> None:
        """Save the farm (a delta of the docs that moved; the drain-stop's
        is a base), then publish offsets and "applied" records."""
        # crash window 1: deltas consumed into the farm, nothing saved —
        # a restart resumes from the OLD offsets and replays the window
        # (ingest skips by sequence number)
        self._fault("stage.pre_checkpoint")
        with span("stage.checkpoint"):
            with span("applier.flush"):
                self.applier.flush()
            # each fences the farm first (applier.finalize)
            self._count(self._ckpt.base() if final else self._ckpt.delta())
            # crash window 2: the farm is saved but the offset
            # checkpoints / "applied" emits are not — the restart
            # replays against a NEWER farm, the skip-by-seq path's
            # hardest case
            self._fault("stage.post_checkpoint")
            with span("stage.publish"):
                self._publish()

    def _count(self, written) -> None:
        self.counters.inc("stage.checkpoint.bytes", written.nbytes)
        self.counters.inc("stage.checkpoint.delta_docs", written.docs)
        self.counters.inc("stage.checkpoint.bases", written.bases)
        a = self.applier
        # the applier's own tallies, as deltas since the last checkpoint
        # (a peak rises by what it rose: the counter reads the peak)
        tallies = []
        if a.long_state is not None:
            self.counters.inc("stage.checkpoint.long_bytes",
                              written.long_bytes)
            tallies += [("applier.promotions", a.promotions),
                        ("applier.long.dispatches", a.long_dispatches),
                        ("applier.long.slots_peak", a.long_slots_peak)]
        if a.grids:
            self.counters.inc("stage.checkpoint.grid_bytes",
                              written.grid_bytes)
            tallies += [("applier.grid.dispatches", a.grid_dispatches),
                        ("applier.grid.cells_set", a.grid_cells_set),
                        ("applier.grid.dead_writes", a.grid_dead_writes),
                        ("applier.grid.handles_peak", a.grid_handles_peak),
                        ("applier.grid.escalations", a.grid_escalations)]
        for name, now in tallies:
            self.counters.inc(name, now - self._tallied.get(name, 0))
            self._tallied[name] = now

    def _publish(self) -> None:
        """Offset checkpoints and one "applied" record per doc whose
        offset moved since the last publish; with tracing armed, each doc
        whose applied seq advanced samples its deli-to-applied lag."""
        now = time.time()
        moved, self._moved = self._moved, {}
        for topic in moved:
            offset = self._offsets[topic]
            tenant, doc = _doc_of(topic)
            self.save_checkpoint(tenant, doc, {"offset": offset})
            seq = max(self._watermarks.get(topic, 0),
                      self.applier.applied_seq(tenant, doc))
            self.emit({"kind": "applied", "tenant": tenant, "doc": doc,
                       "applied_seq": seq})
            if seq > self._published.get(topic, 0):
                self._published[topic] = seq
                stamp = self._stamps.get(topic)
                if stamp:
                    sample("stage.deli_to_applied.ms", (now - stamp) * 1e3)
        self.counters.inc("stage.publish.records", 2 * len(moved))


STAGES = {"scribe": ScribeStage, "applier": ApplierStage}


def main() -> None:
    parser = argparse.ArgumentParser(description="pipeline stage process")
    parser.add_argument("--stage", choices=sorted(STAGES), required=True)
    parser.add_argument("--log-dir", required=True,
                        help="the core's durable log directory (read-only)")
    parser.add_argument("--state-dir", required=True,
                        help="this stage's own writable log directory")
    parser.add_argument("--partition", default=None, metavar="K/N",
                        help="own only docs with doc_partition == K of N "
                             "(N stage processes split the doc space)")
    parser.add_argument("--trace-dir", default=None, metavar="DIR",
                        help="applier: SIGUSR1 starts the profiler and "
                             "the program's spans, SIGUSR2 writes both "
                             "under DIR and prints SPANS <path>")
    args = parser.parse_args()
    signal.signal(signal.SIGTERM, lambda *a: os._exit(0))
    partition = None
    if args.partition:
        k, _, n = args.partition.partition("/")
        partition = (int(k), int(n))
    if args.stage == "applier":
        ApplierStage.serve(args.log_dir, args.state_dir, partition,
                           trace_dir=args.trace_dir)
        return
    stage = STAGES[args.stage](args.log_dir, args.state_dir,
                               partition=partition)
    print("READY", flush=True)
    stage.run_forever()

if __name__ == "__main__":
    main()
