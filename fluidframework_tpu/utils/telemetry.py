"""Telemetry: namespaced logger + metrics + trace hops + spans.

Ref: packages/utils/telemetry-utils/src/logger.ts — ChildLogger
namespacing (:239), MultiSinkLogger (:283), PerformanceEvent scoped
timing (:434); server metric counters (services/src/metricClient.ts:7);
wire-level trace hops consumed for per-hop latency
(protocol-definitions/src/protocol.ts:59, deli stamping).

Differences by design: sinks are plain callables (no transport baked
in), and the trace consumer turns the hops deli already stamps into the
per-hop latency breakdown the load benches report — the reference
stamps traces but ships them to an external telegraf; here the
aggregation is in-process and queryable. The reference's scoped
``PerformanceEvent`` is :func:`span` here: named from the closed
:data:`SPANS` catalog, free when tracing is off, and written out once
per traced window (see "spans" below).
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time
from collections import defaultdict
from typing import Any, Callable, Optional

Sink = Callable[[dict], None]

# --------------------------------------------------------------- hop catalog
#
# The per-tier trace-hop vocabulary. Columnar wire frames carry hops
# as compact (hop id, timestamp) pairs (the binwire hoptail); rec
# frames carry the (service, action) strings. Both sides map through
# THIS table — it is the catalog's single source of truth — and the
# breakdown pair names (``submit_to_deli``, ``deli_to_ack``,
# ``admit_to_deli``, …) derive from the SHORT keys of consecutive
# PRESENT hops, so the legacy two-pair split falls out as the special
# case where only client/submit and deli/sequence are stamped.
#
# STABILITY: hop ids are WIRE values (hoptail u8, durable replays,
# mixed-version gateways) — existing ids are FROZEN and new hops are
# APPENDED, never inserted. Numeric id order therefore stopped
# matching path order at id 6; the pipeline position lives in
# HOP_PIPELINE below, and every ordering consumer sorts by that.
HOPS = (
    ("client", "submit", "submit"),
    ("gateway", "relay", "relay"),
    ("frontend", "admit", "admit"),
    ("deli", "sequence", "deli"),
    ("broadcast", "fanout", "fanout"),
    ("client", "ack", "ack"),
    # -- appended (PR 14): ids 6+ are newer than some stampers --
    ("frontend", "shed", "shed"),      # driver parked the op on a shed nack
    ("applier", "stage", "stage"),     # host half of a dispatch wave
    ("applier", "execute", "execute"),  # device half of a dispatch wave
)
(HOP_SUBMIT, HOP_RELAY, HOP_ADMIT, HOP_DELI, HOP_FANOUT,
 HOP_ACK, HOP_SHED, HOP_STAGE, HOP_EXECUTE) = range(len(HOPS))
#: hop id → (service, action) — the rec-frame string pair.
HOP_SERVICE_ACTION = tuple((s, a) for s, a, _ in HOPS)
#: (service, action) → hop id.
HOP_ID = {(s, a): i for i, (s, a, _) in enumerate(HOPS)}
#: hop id → short key used in breakdown pair names.
HOP_SHORT = tuple(short for _, _, short in HOPS)
#: Hop ids in PIPELINE order — shed precedes submit (the park happens
#: before the retry-flush restamps submit), stage/execute sit between
#: sequencing and fan-out (the applier consumes the sequenced stream).
HOP_PIPELINE = (HOP_SHED, HOP_SUBMIT, HOP_RELAY, HOP_ADMIT, HOP_DELI,
                HOP_STAGE, HOP_EXECUTE, HOP_FANOUT, HOP_ACK)
#: hop id → pipeline position (the sort key for breakdown legs).
HOP_ORDER = {h: i for i, h in enumerate(HOP_PIPELINE)}


def hop_pair_name(a: int, b: int) -> str:
    """The breakdown key for the leg between two hop ids."""
    return f"{HOP_SHORT[a]}_to_{HOP_SHORT[b]}"


def hop_pairs(hops) -> list[tuple[str, float]]:
    """[(hop_id, ts), ...] → [(pair_name, delta_ms), ...] between
    consecutive PRESENT hops in pipeline order (unknown ids ignored;
    a repeated id keeps its last timestamp — EXCEPT gateway/relay,
    where every stamp is kept in arrival order: stacked relay tiers
    each stamp the same id, so the repeats ARE the relay depth and
    each inter-tier leg surfaces as a ``relay_to_relay`` pair)."""
    ts_by_id: dict[int, float] = {}
    relays: list[float] = []
    for i, ts in hops:
        if not 0 <= i < len(HOPS):
            continue
        if i == HOP_RELAY:
            relays.append(ts)
        else:
            ts_by_id[i] = ts
    seq: list[tuple[int, float]] = []
    for h in HOP_PIPELINE:
        if h == HOP_RELAY:
            seq.extend((HOP_RELAY, ts) for ts in relays)
        elif h in ts_by_id:
            seq.append((h, ts_by_id[h]))
    return [(hop_pair_name(a, b), (tb - ta) * 1e3)
            for (a, ta), (b, tb) in zip(seq, seq[1:])]


def count_unknown_hops(hops) -> int:
    """Entries whose id falls outside the catalog — a version-skewed
    stamper. Callers surface the count as ``obs.trace.unknown_hops``
    (this module sits below obs/, so it cannot reach the registry)."""
    return sum(1 for i, _ in hops if not 0 <= i < len(HOPS))


# -------------------------------------------------------------------- spans
#
# One span primitive for the whole program. ``with span("stage.poll"):``
# times a piece of work in the process that does it. Names form the
# closed catalog below (the fluidlint ``metric-name`` pass rejects a
# literal ``span("...")`` name that is not in it); SAMPLES names the
# observations an armed trace keeps every value of.
#
# Disarmed (the default) ``span`` is one module-global test returning a
# shared no-op context manager: no allocation, no clock read, no lock.
# ``tracing_start(dir, role)`` arms it: each span then records (name,
# start wall ns, duration ns, parent index, thread id) into a bounded
# in-memory list — past DEFAULT_SPAN_CAPACITY entries new spans are
# counted as overflow, never stored — and, in a process that has
# imported JAX, also opens a ``jax.profiler.TraceAnnotation`` of the
# same name, so the span lands on the profiler's trace beside the
# device ops. Arming there also marks the window's start with a
# ``trace.window`` annotation, at the wall ns the output keeps as its
# anchor: whoever reads the profile finds that annotation, and the
# difference of the two starts maps wall ns onto the profile's clock.
# ``tracing_stop()`` writes everything once, to
# ``dir/spans-<role>-<pid>.json``: the spans (those still open cut at the
# stop and listed under ``open``), the samples, the window's
# deltas of the counters handed to ``tracing_start``, and the anchor.

#: span name -> what it times. A pure literal (fluidlint reads it).
SPANS = {
    # applier stage (service/stage_runner.py)
    "stage.poll": "refresh of the shared log's subscribed topics",
    "stage.drain": "delivery of the new records into the applier",
    "stage.checkpoint": "ApplierStage.checkpoint, the whole call",
    "stage.publish": "offset checkpoints and 'applied' records",
    "stage.state_flush": "flush of the stage's own log",
    # device applier (service/tpu_applier.py)
    "applier.flush": "dispatch of every staged op (stage checkpoint)",
    "applier.fence": "finalize: wait for the device, read overflow",
    "applier.stage_wave": "host half of a wave: pack, scatter, put",
    "applier.dispatch": "enqueue of one jitted step (asynchronous)",
    "applier.promote": "one farm doc moved to the long tier on the device",
    "applier.long_stage_wave": "host half of a long-tier wave: rows, put",
    "applier.long_dispatch": "enqueue of one long-tier step (asynchronous)",
    "applier.grid_stage_wave": "host half of a grid cells wave: rows, put",
    "applier.grid_dispatch": "enqueue of one matrix_cells step "
                             "(asynchronous)",
    "checkpoint.save": "a base: save_applier_checkpoint, or its write "
                       "on the applier-base thread",
    "checkpoint.device_get": "copy of the state arrays to the host",
    "checkpoint.write_arrays": "savez_compressed of the arrays + rename",
    "checkpoint.write_meta": "JSON sidecar: dump, rename",
    "checkpoint.delta": "a delta: the moved slots' rows and sidecar",
    "checkpoint.delta.device_get": "copy of the state arrays to the host",
    "checkpoint.delta.write": "frame and append the delta record",
    # durable log (service/durable_log.py)
    "log.flush": "DurableLog.flush",
    # socket doors (service/front_end.py)
    "frontend.ingress_batch": "one drain-batched wave of frames",
    "frontend.backchannel": "one backchannel pass that moved records",
    "frontend.summarize_pass": "one pass of the summarizer loop",
    "frontend.presence_flush": "one presence-lane flush tick",
    "frontend.gc_sweep": "the timed cycle-collector sweep",
    "frontend.pipeline_checkpoint": "one slice of the paced pipeline "
                                    "checkpoint",
    "frontend.loop_stall": "a serving-loop wake-up late past 20 ms",
}

#: sample name -> what one value is; an armed trace keeps every value.
SAMPLES = {
    "frontend.loop.lag_ms": "how late the 10 ms loop ticker woke, ms",
    "stage.deli_to_applied.ms": "deli stamp to 'applied' record, ms",
}

#: the profiler annotation that marks the traced window's start (the
#: anchor)
WINDOW_SPAN = "trace.window"

#: spans an armed window stores; later ones count as overflow
DEFAULT_SPAN_CAPACITY = 1 << 20


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _Trace:
    """The armed recording of one window (see the section comment)."""

    def __init__(self, directory: str, role: str, counters: tuple):
        self.directory, self.role = directory, role
        self.capacity = DEFAULT_SPAN_CAPACITY
        self.spans: list = []  # [name, start_ns, dur_ns, parent, tid]
        self.samples: dict = {}
        self.overflow = 0
        self.lock = threading.Lock()
        self.local = threading.local()
        self.counters = counters
        self.counts0 = [dict(c._counts) for c in counters]
        jax = sys.modules.get("jax")
        self.annotate = jax.profiler.TraceAnnotation if jax else None
        self.anchor_wall_ns = time.time_ns()
        if self.annotate is not None:
            with self.annotate(WINDOW_SPAN):
                pass

    def stack(self) -> list:
        s = getattr(self.local, "stack", None)
        if s is None:
            s = self.local.stack = []
        return s


class _Span:
    __slots__ = ("trace", "name", "idx", "ann")

    def __init__(self, trace: _Trace, name: str):
        if name not in SPANS:
            raise ValueError(f"span {name!r} is not in telemetry.SPANS")
        self.trace, self.name, self.ann = trace, name, None

    def __enter__(self):
        tr = self.trace
        stack = tr.stack()
        rec = [self.name, 0, -1, stack[-1] if stack else -1,
               threading.get_ident()]
        with tr.lock:
            idx = len(tr.spans)
            if idx < tr.capacity:
                tr.spans.append(rec)
            else:
                tr.overflow += 1
                idx = -1
        self.idx = idx
        if idx >= 0:
            stack.append(idx)
        if tr.annotate is not None:
            self.ann = tr.annotate(self.name)
            self.ann.__enter__()
        rec[1] = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t = time.time_ns()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        if self.idx >= 0:
            rec = self.trace.spans[self.idx]
            rec[2] = t - rec[1]
            stack = self.trace.stack()
            if stack and stack[-1] == self.idx:
                stack.pop()
            elif self.idx in stack:
                stack.remove(self.idx)
        return False


_ARMED: Optional[_Trace] = None


def span(name: str):
    """Time the ``with`` block as span ``name`` (a :data:`SPANS` key)
    when tracing is armed; a shared no-op otherwise."""
    t = _ARMED
    if t is None:
        return _NO_SPAN
    return _Span(t, name)


def record_span(name: str, start_ns: int, dur_ns: int) -> None:
    """Record a span measured after the fact (a late loop wake-up), with
    no parent; nothing when disarmed."""
    t = _ARMED
    if t is None:
        return
    if name not in SPANS:
        raise ValueError(f"span {name!r} is not in telemetry.SPANS")
    with t.lock:
        if len(t.spans) < t.capacity:
            t.spans.append([name, int(start_ns), int(dur_ns), -1,
                            threading.get_ident()])
        else:
            t.overflow += 1


def sample(name: str, value: float) -> None:
    """Keep one value of observation ``name`` (a :data:`SAMPLES` key)
    in the armed trace; nothing when disarmed."""
    t = _ARMED
    if t is None:
        return
    if name not in SAMPLES:
        raise ValueError(f"sample {name!r} is not in telemetry.SAMPLES")
    with t.lock:
        t.samples.setdefault(name, []).append([time.time_ns(), value])


def tracing_armed() -> bool:
    return _ARMED is not None


def tracing_start(directory: str, role: str, counters=()) -> None:
    """Arm span recording for this process until :func:`tracing_stop`.
    ``counters`` are :class:`Counters` whose deltas over the window the
    output keeps. Where the JAX profiler is to hold the spans, start it
    first."""
    global _ARMED
    if _ARMED is not None:
        raise RuntimeError("tracing is already armed")
    _ARMED = _Trace(directory, role, tuple(counters))


def tracing_stop() -> Optional[str]:
    """Disarm and write the window out; returns the file's path (None
    when tracing was not armed). Spans still open are cut at the stop
    and listed by index under ``open``: whole-span statistics leave them
    out, a timeline keeps them. Call it from the thread that runs the
    spans' work (not from a signal handler, which can land while a span
    holds the window's lock)."""
    global _ARMED
    t = _ARMED
    if t is None:
        return None
    _ARMED = None
    stop_ns = time.time_ns()
    with t.lock:
        spans = [list(s) for s in t.spans]
    still_open = [i for i, s in enumerate(spans) if s[2] < 0]
    for i in still_open:
        spans[i][2] = max(0, stop_ns - spans[i][1])
    counts = {}
    for c, before in zip(t.counters, t.counts0):
        for name, v in list(c._counts.items()):
            counts[name] = counts.get(name, 0) + v - before.get(name, 0)
    anchor = {"wall_ns": t.anchor_wall_ns, "stop_wall_ns": stop_ns}
    doc = {"role": t.role, "pid": os.getpid(), "capacity": t.capacity,
           "overflow": t.overflow, "anchor": anchor, "spans": spans,
           "open": still_open, "samples": t.samples, "counters": counts}
    os.makedirs(t.directory, exist_ok=True)
    path = os.path.join(t.directory, f"spans-{t.role}-{os.getpid()}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(doc, f)
    os.replace(path + ".tmp", path)
    return path


def percentile(sorted_vals: list[float], p: float) -> float:
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1,
            max(0, int(round(p * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


class TelemetryLogger:
    """Namespaced event logger with injectable sinks.

    ``child("deli")`` shares the sink chain and prefixes the namespace —
    the ChildLogger pattern. Events are dicts with at least
    ``{"category", "event", "namespace", "ts"}``.
    """

    def __init__(self, namespace: str = "", sinks: Optional[list[Sink]] = None):
        self.namespace = namespace
        self._sinks: list[Sink] = sinks if sinks is not None else []

    def child(self, namespace: str) -> "TelemetryLogger":
        ns = f"{self.namespace}:{namespace}" if self.namespace else namespace
        out = TelemetryLogger(ns)
        out._sinks = self._sinks  # shared chain: adding a sink later
        return out                # reaches existing children too

    def add_sink(self, sink: Sink) -> None:
        self._sinks.append(sink)

    def send(self, category: str, event: str, **fields: Any) -> None:
        if not self._sinks:
            return
        record = {"category": category, "event": event,
                  "namespace": self.namespace, "ts": time.time(), **fields}
        for sink in self._sinks:
            sink(record)

    def info(self, event: str, **fields: Any) -> None:
        self.send("generic", event, **fields)

    def error(self, event: str, **fields: Any) -> None:
        self.send("error", event, **fields)


class BufferSink:
    """Ring-buffer sink for tests and the /repl-style debug surface."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self.records: list[dict] = []

    def __call__(self, record: dict) -> None:
        self.records.append(record)
        if len(self.records) > self.capacity:
            del self.records[: len(self.records) - self.capacity]

    def of(self, event: str) -> list[dict]:
        return [r for r in self.records if r["event"] == event]


class Counters:
    """Named monotonic counters + value observations (metricClient role).

    Value series are bounded: each keeps a ``max_samples`` reservoir
    (uniform reservoir sampling, seeded so snapshots are reproducible)
    plus the true observation count — a long-running service observing
    per-op latencies must not grow a list per op forever. ``count`` in
    the snapshot is always the TRUE number of observations, not the
    reservoir size.
    """

    def __init__(self, max_samples: int = 4096):
        self._counts: dict[str, int] = defaultdict(int)
        self._values: dict[str, list[float]] = defaultdict(list)
        self._observed: dict[str, int] = defaultdict(int)
        self._max_samples = max_samples
        self._rng = random.Random(0)

    def inc(self, name: str, by: int = 1) -> None:
        self._counts[name] += by

    def observe(self, name: str, value: float) -> None:
        n = self._observed[name] = self._observed[name] + 1
        vals = self._values[name]
        if len(vals) < self._max_samples:
            vals.append(value)
        else:
            j = self._rng.randrange(n)
            if j < self._max_samples:
                vals[j] = value

    def snapshot(self) -> dict:
        out: dict[str, Any] = dict(self._counts)
        for name, vals in self._values.items():
            s = sorted(vals)
            series: dict[str, Any] = {
                "count": self._observed[name],
                "p50": round(percentile(s, 0.5), 3),
                "p99": round(percentile(s, 0.99), 3),
            }
            if name in self._counts:
                # a counter and a value series share the name: surface
                # both under the key instead of the series silently
                # clobbering the counter (or vice versa)
                series["counter"] = self._counts[name]
            out[name] = series
        return out


class TraceAggregator:
    """Consume wire trace hops into an ordered hop-pair breakdown.

    Each tier stamps its hop from the :data:`HOPS` catalog (client/
    submit, gateway/relay, frontend/admit, deli/sequence, broadcast/
    fanout); the ack observer calls ``record(msg)`` when its own op
    comes back. Every leg between consecutive PRESENT hops becomes a
    ``{a}_to_{b}`` latency series — partial stamping (only client+deli)
    reproduces the legacy submit→deli / deli→ack split exactly.
    """

    def __init__(self):
        self._hops: dict[str, list[float]] = defaultdict(list)
        #: hops dropped for an id outside the catalog — a
        #: version-skewed stamper; surfaced in ``report()`` (and by
        #: service consumers as ``obs.trace.unknown_hops``) instead of
        #: vanishing silently.
        self.unknown_hops = 0

    def record(self, msg, ack_time: Optional[float] = None) -> None:
        hops = []
        for hop in msg.traces:
            i = HOP_ID.get((hop.service, hop.action))
            if i is not None:
                hops.append((i, hop.timestamp))
            else:
                self.unknown_hops += 1
        self.record_hops(
            hops, ack_time if ack_time is not None else time.time())

    def record_hops(self, hops, ack_time: Optional[float] = None) -> None:
        """Fold an ordered [(hop_id, timestamp), ...] list (the wire
        hoptail shape) into the breakdown.

        ``ack_time`` contributes the client/ack hop — but only when the
        op was actually sequenced (a deli-or-later hop is present): an
        op that never reached the sequencer has no ack latency to
        attribute, so a lone client/submit stamp records nothing.
        """
        known = [(i, ts) for i, ts in hops if 0 <= i < len(HOPS)]
        self.unknown_hops += len(hops) - len(known)
        # "actually sequenced" means deli-or-later in PIPELINE order —
        # appended ids like frontend/shed are numerically past deli but
        # sit before it on the path, so numeric comparison would lie
        deli_pos = HOP_ORDER[HOP_DELI]
        if (ack_time is not None
                and all(i != HOP_ACK for i, _ in known)
                and any(HOP_ORDER[i] >= deli_pos for i, _ in known)):
            known.append((HOP_ACK, ack_time))
        for name, ms in hop_pairs(known):
            self._hops[name].append(ms)

    def merge_raw(self, hops: dict[str, list[float]]) -> None:
        for name, vals in hops.items():
            self._hops[name].extend(vals)

    @property
    def raw(self) -> dict[str, list[float]]:
        return dict(self._hops)

    def report(self) -> dict:
        out = {}
        for name, vals in self._hops.items():
            s = sorted(vals)
            out[name] = {"count": len(s),
                         "p50_ms": round(percentile(s, 0.5), 3),
                         "p99_ms": round(percentile(s, 0.99), 3)}
        if self.unknown_hops:
            out["unknown_hops"] = {"count": self.unknown_hops}
        return out
