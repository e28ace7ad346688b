"""Telemetry: logger namespacing/sinks, perf events, counters, and the
wire-trace consumer producing per-hop latency (SURVEY §5.1/§5.5).

Ref: telemetry-utils/src/logger.ts (ChildLogger :239, PerformanceEvent
:434, here ``span``), services/src/metricClient.ts, protocol ITrace
hops.
"""

import json
import time

import pytest

from fluidframework_tpu.protocol.messages import (
    MessageType,
    SequencedDocumentMessage,
    TraceHop,
)
from fluidframework_tpu.utils import (
    BufferSink,
    Counters,
    TelemetryLogger,
    TraceAggregator,
)
from fluidframework_tpu.utils.telemetry import span, tracing_start, tracing_stop


def test_child_logger_namespacing_and_shared_sinks():
    sink = BufferSink()
    root = TelemetryLogger("service", sinks=[sink])
    deli = root.child("deli")
    deli.info("nack", code=400)
    root.error("boom")
    assert sink.records[0]["namespace"] == "service:deli"
    assert sink.records[0]["code"] == 400
    assert sink.records[1]["category"] == "error"
    # a sink added at the root AFTER child creation reaches the child
    late = BufferSink()
    root.add_sink(late)
    deli.info("again")
    assert late.of("again")


def test_sinkless_logger_is_free_and_silent():
    log = TelemetryLogger("x")
    log.info("anything", heavy=object())  # must not raise or format


@pytest.mark.parametrize("raises", [False, True])
def test_span_duration_and_exception(tmp_path, raises):
    """The scoped timer (PerformanceEvent's role): an armed span records
    its duration whether the block returns or raises, and the exception
    still propagates."""
    tracing_start(str(tmp_path), "t")
    try:
        with span("stage.poll"):
            time.sleep(0.01)
            if raises:
                raise ValueError("nope")
    except ValueError as e:
        assert raises and "nope" in str(e)
    with open(tracing_stop()) as f:
        (rec,) = json.load(f)["spans"]
    assert rec[0] == "stage.poll" and rec[2] >= 8e6


def test_counters_snapshot_percentiles():
    c = Counters()
    c.inc("ops", 3)
    for v in range(100):
        c.observe("lat", float(v))
    snap = c.snapshot()
    assert snap["ops"] == 3
    assert snap["lat"]["count"] == 100
    assert 45 <= snap["lat"]["p50"] <= 55


def test_counters_name_collision_surfaces_both():
    """A counter and a value series sharing a name must both survive the
    snapshot — the series reports under the key with the counter beside
    it instead of one silently clobbering the other."""
    c = Counters()
    c.inc("chaos.injected", 2)
    c.observe("chaos.injected", 1.5)
    c.observe("chaos.injected", 2.5)
    snap = c.snapshot()
    assert snap["chaos.injected"]["count"] == 2
    assert snap["chaos.injected"]["counter"] == 2
    assert snap["chaos.injected"]["p50"] in (1.5, 2.5)


def test_counters_reservoir_is_bounded_and_deterministic():
    c = Counters(max_samples=64)
    for v in range(10_000):
        c.observe("lat", float(v))
    assert len(c._values["lat"]) == 64  # bounded, not 10k
    snap = c.snapshot()
    assert snap["lat"]["count"] == 10_000  # true total, not reservoir size
    # uniform reservoir: p50 lands near the middle of the range
    assert 2_000 <= snap["lat"]["p50"] <= 8_000
    # seeded: a second identical run snapshots identically
    c2 = Counters(max_samples=64)
    for v in range(10_000):
        c2.observe("lat", float(v))
    assert c2.snapshot() == snap


def _msg(traces):
    return SequencedDocumentMessage(
        client_id="c", sequence_number=1, minimum_sequence_number=0,
        client_sequence_number=1, reference_sequence_number=0,
        type=MessageType.OPERATION, traces=traces)


def test_trace_aggregator_per_hop_split():
    agg = TraceAggregator()
    t0 = 1000.0
    agg.record(_msg([
        TraceHop("client", "submit", t0),
        TraceHop("deli", "sequence", t0 + 0.004),
    ]), ack_time=t0 + 0.010)
    rep = agg.report()
    assert abs(rep["submit_to_deli"]["p50_ms"] - 4.0) < 0.01
    assert abs(rep["deli_to_ack"]["p50_ms"] - 6.0) < 0.01


def test_trace_aggregator_missing_hops():
    """Partial stamping must not poison the split: no deli hop → nothing
    recorded; a deli hop without the client submit hop still yields the
    deli→ack leg (the server stamped it, the client didn't)."""
    agg = TraceAggregator()
    agg.record(_msg([TraceHop("client", "submit", 1000.0)]),
               ack_time=1000.5)
    assert agg.report() == {}
    agg.record(_msg([TraceHop("deli", "sequence", 1000.0)]),
               ack_time=1000.002)
    rep = agg.report()
    assert "submit_to_deli" not in rep
    assert rep["deli_to_ack"]["count"] == 1
    agg.record(_msg([]), ack_time=1001.0)  # no traces at all: a no-op
    assert agg.report()["deli_to_ack"]["count"] == 1


def test_trace_aggregator_merge_raw_and_percentiles():
    """merge_raw folds a worker's raw hop lists into the parent (the
    cross-process aggregation path) and report() percentiles span the
    merged population."""
    a, b = TraceAggregator(), TraceAggregator()
    t0 = 2000.0
    for i in range(10):
        a.record(_msg([TraceHop("client", "submit", t0),
                       TraceHop("deli", "sequence", t0 + 0.001 * (i + 1))]),
                 ack_time=t0 + 0.05)
    b.merge_raw(a.raw)
    b.merge_raw({"submit_to_deli": [100.0], "custom_hop": [7.0]})
    rep = b.report()
    assert rep["submit_to_deli"]["count"] == 11
    assert rep["custom_hop"] == {"count": 1, "p50_ms": 7.0, "p99_ms": 7.0}
    # p50 from the 1..10ms ramp; p99 pulled up by the merged outlier
    assert 4.0 <= rep["submit_to_deli"]["p50_ms"] <= 7.0
    assert rep["submit_to_deli"]["p99_ms"] == 100.0


def test_deli_stamps_ride_to_clients_and_aggregate():
    """End-to-end: submit through the real pipeline; the broadcast op
    carries client+deli+fanout hops and the aggregator splits the
    latency into every stamped leg."""
    from fluidframework_tpu.protocol.messages import DocumentMessage
    from fluidframework_tpu.service import LocalServer

    server = LocalServer()
    agg = TraceAggregator()
    conn = server.connect("t", "doc")
    acked = []
    conn.on_ops = lambda batch: [
        (agg.record(m), acked.append(m))
        for m in batch if m.client_id == conn.client_id
    ]
    conn.submit([DocumentMessage(
        client_sequence_number=1, reference_sequence_number=0,
        type=MessageType.OPERATION, contents={"x": 1},
        traces=[TraceHop("client", "submit", time.time())])])
    assert acked
    rep = agg.report()
    assert rep["submit_to_deli"]["count"] == 1
    assert rep["deli_to_fanout"]["count"] == 1
    assert rep["fanout_to_ack"]["count"] == 1


def test_deli_nacks_and_evictions_are_logged():
    from fluidframework_tpu.protocol.messages import DocumentMessage
    from fluidframework_tpu.service import LocalServer

    sink = BufferSink()
    now = [0.0]
    server = LocalServer(clock=lambda: now[0], client_timeout=10.0,
                         logger=TelemetryLogger("svc", sinks=[sink]))
    conn = server.connect("t", "doc")
    # clientSeq gap → nack, logged with the reason
    conn.submit([DocumentMessage(
        client_sequence_number=5, reference_sequence_number=0,
        type=MessageType.OPERATION, contents={})])
    (nack,) = sink.of("nack")
    assert "gap" in nack["reason"] and nack["namespace"].endswith("deli")
    # idle expiry logged
    now[0] = 100.0
    server.expire_idle_clients()
    assert sink.of("idle_client_evicted")
